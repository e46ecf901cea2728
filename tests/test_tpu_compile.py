"""The main path's Pallas kernels, compiled for a described TPU v5e at
real widths (Nemotron-4-15B for the plain MLP and attention, a
4096/11008 gated MLP, a 512-wide latent cache).

Nothing runs: the TPU compiler, which is installed here, compiles for a
chip that is described and not attached. It refuses what interpret mode
cannot see: tiles that break the TPU's (8, 128) layout rule and kernels
that ask for more VMEM than the compiler grants. The topology is
described inside a fixture, never at import, so every test worker
collects the same tests and only the one running this file loads the
TPU library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_gqa_kernel, paged_mla_kernel
from repro.kernels.sidebar_gated_mlp import sidebar_gated_mlp
from repro.kernels.sidebar_mlp import sidebar_mlp, sidebar_mlp_pipelined


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text       # the kernel, not a fallback


BF16, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32
D, F = 6144, 24576                         # Nemotron-4-15B


@pytest.mark.parametrize("rows", [8, 256])
@pytest.mark.parametrize("depth", [1, 2], ids=["serial", "depth2"])
def test_sidebar_mlp_compiles_at_nemotron_widths(one_chip, depth, rows):
    if depth == 1:
        fn = functools.partial(sidebar_mlp, activation="squared_relu")
    else:
        fn = functools.partial(sidebar_mlp_pipelined,
                               activation="squared_relu", depth=depth)
    _compile(fn, one_chip, ((rows, D), BF16), ((D, F), BF16),
             ((F, D), BF16))


@pytest.mark.parametrize("rows", [8, 256])
def test_sidebar_gated_mlp_compiles(one_chip, rows):
    d, f = 4096, 11008
    _compile(functools.partial(sidebar_gated_mlp, activation="silu"),
             one_chip, ((rows, d), BF16), ((d, f), BF16), ((d, f), BF16),
             ((f, d), BF16))


_GQA_SHAPES = {                            # b, h, hkv, pool, nb
    "": (8, 48, 8, 1024, 100),
    "-nemotron8": (32, 48, 8, 11405, 128),  # the Nemotron-4-15B decode cell
    "-deepseek8": (16, 32, 32, 4420, 256),  # the DeepSeek-7B sessions cell
}


@pytest.mark.parametrize("kv, b, h, hkv, pool, nb", [
    pytest.param(kv, *shape, id=name + where)
    for where, shape in _GQA_SHAPES.items()
    for name, kv in (("bf16", BF16), ("int8", jnp.int8))
])
def test_paged_gqa_kernel_compiles(one_chip, kv, b, h, hkv, pool, nb):
    bs, dh = 16, 128
    shapes = [((b, h, dh), BF16), ((pool, hkv, bs, dh), kv),
              ((pool, hkv, bs, dh), kv), ((b, nb), I32), ((b,), I32)]
    if kv == jnp.int8:
        shapes += [((pool, hkv, bs), F32)] * 2

        def fn(q, k, v, t, n, ks, vs):
            return paged_gqa_kernel(q, k, v, t, n, scale=dh ** -0.5,
                                    k_scale=ks, v_scale=vs)
    else:
        fn = functools.partial(paged_gqa_kernel, scale=dh ** -0.5)
    _compile(fn, one_chip, *shapes)


def test_paged_mla_kernel_compiles(one_chip):
    b, h, kvr, rope, bs, pool, nb = 8, 128, 512, 64, 16, 1024, 100
    _compile(functools.partial(paged_mla_kernel, scale=(kvr + rope) ** -0.5),
             one_chip, ((b, h, kvr), F32), ((b, h, rope), BF16),
             ((pool, bs, kvr), BF16), ((pool, bs, rope), BF16),
             ((b, nb), I32), ((b,), I32))


def test_flash_attention_compiles(one_chip):
    s, dh = 2048, 128
    _compile(functools.partial(flash_attention, causal=True), one_chip,
             ((1, 48, s, dh), BF16), ((1, 8, s, dh), BF16),
             ((1, 8, s, dh), BF16))
