"""Per-kernel shape/dtype sweeps against the pure-jnp oracles.

Every Pallas kernel runs in interpret mode (kernel body executed in
Python on CPU) and must match ref.py to tight tolerances.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.function_table import make_default_table
from repro.kernels import ops, ref
from repro.kernels.sidebar_mlp import choose_tiles, vmem_bytes
from repro.core import constants

RNG = np.random.default_rng(42)


def _arr(shape, dtype=jnp.float32, scale=0.1):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


MLP_SHAPES = [(8, 128, 128), (16, 128, 256), (32, 256, 512), (64, 384, 128)]
ACTS = ["relu", "softplus", "silu", "gelu", "squared_relu", "tanh"]


@pytest.mark.parametrize("shape", MLP_SHAPES)
@pytest.mark.parametrize("act", ["relu", "softplus", "silu"])
def test_sidebar_mlp_sweep(shape, act):
    m, d, f = shape
    x, w1, w2 = _arr((m, d)), _arr((d, f), scale=0.05), _arr((f, d), scale=0.05)
    got = ops.sidebar_mlp(x, w1, w2, act, interpret=True, use_kernel=True)
    want = ref.sidebar_mlp_ref(x, w1, w2, act)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sidebar_mlp_dtypes(dtype):
    x, w1, w2 = _arr((16, 128), dtype), _arr((128, 256), dtype, 0.05), \
        _arr((256, 128), dtype, 0.05)
    got = ops.sidebar_mlp(x, w1, w2, "relu", interpret=True, use_kernel=True)
    want = ref.sidebar_mlp_ref(x, w1, w2, "relu")
    tol = 3e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_sidebar_mlp_function_table_swap():
    """New activation = one table row, same kernel source (paper claim)."""
    table = make_default_table()
    x, w1, w2 = _arr((16, 128)), _arr((128, 256), scale=0.05), \
        _arr((256, 128), scale=0.05)
    table.register("mish", lambda v: v * jnp.tanh(jnp.logaddexp(v, 0.0)))
    got = ops.sidebar_mlp(x, w1, w2, "mish", table=table, interpret=True,
                          use_kernel=True)
    want = ref.sidebar_mlp_ref(x, w1, w2, "mish", table)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


def test_choose_tiles_respects_vmem():
    """Planned tiles fit the compiler's scoped-VMEM limit with every
    blocked operand double-buffered (what the compiler allocates; the
    real compiles are in test_tpu_compile.py)."""
    for d in (512, 1024, 4096, 6144, 8192):
        for sidebars in (1, 2):
            bm, bf = choose_tiles(1024, d, 4 * d, itemsize=2,
                                  sidebars=sidebars)
            assert vmem_bytes(bm, bf, d, d, 2, sidebars=sidebars) \
                <= constants.SCOPED_VMEM_BYTES
    # at D = 16384 not even (8, 128) tiles fit with D resident
    assert choose_tiles(1024, 16384, 4 * 16384, itemsize=2) is None


@pytest.mark.parametrize("shape", [(32, 128, 128), (64, 256, 384),
                                   (128, 512, 128)])
@pytest.mark.parametrize("act", ["identity", "gelu"])
def test_sidebar_matmul_sweep(shape, act):
    m, k, n = shape
    a, b = _arr((m, k)), _arr((k, n))
    got = ops.sidebar_matmul(a, b, act, interpret=True, use_kernel=True)
    want = ref.sidebar_matmul_ref(a, b, act)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("act", ACTS)
def test_host_activation_sweep(act):
    x = _arr((64, 512), scale=1.0)
    got = ops.host_activation(x, act, interpret=True, use_kernel=True)
    want = ref.activation_ref(x, act)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_host_activation_rowwise_softmax():
    x = _arr((32, 384), scale=1.0)
    got = ops.host_activation(x, "softmax", interpret=True, use_kernel=True)
    want = ref.activation_ref(x, "softmax")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got).sum(-1), 1.0, rtol=1e-5)


FLASH_CASES = [
    # (B, Hq, Hkv, S, T, Dh, causal)
    (2, 4, 4, 128, 128, 64, True),
    (1, 8, 2, 128, 128, 64, True),     # GQA
    (2, 4, 2, 128, 256, 32, True),     # decode-style offset
    (1, 4, 4, 128, 128, 128, False),   # non-causal (cross-attn)
    (1, 2, 1, 256, 256, 64, True),     # multiple q blocks
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_sweep(case):
    b, hq, hkv, s, t, dh, causal = case
    q = _arr((b, hq, s, dh), scale=0.3)
    k = _arr((b, hkv, t, dh), scale=0.3)
    v = _arr((b, hkv, t, dh), scale=0.3)
    got = ops.flash_attention(q, k, v, causal=causal, interpret=True,
                              use_kernel=True, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def test_flash_attention_bf16():
    q = _arr((1, 4, 128, 64), jnp.bfloat16, 0.3)
    k = _arr((1, 4, 128, 64), jnp.bfloat16, 0.3)
    v = _arr((1, 4, 128, 64), jnp.bfloat16, 0.3)
    got = ops.flash_attention(q, k, v, causal=True, interpret=True,
                              use_kernel=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_flash_rejects_bad_gqa():
    q = _arr((1, 3, 128, 64))
    k = _arr((1, 2, 128, 64))
    with pytest.raises(ValueError, match="GQA"):
        ops.flash_attention(q, k, k, interpret=True, use_kernel=True)


GATED_SHAPES = [(8, 128, 128), (16, 128, 256), (32, 256, 512)]


@pytest.mark.parametrize("shape", GATED_SHAPES)
@pytest.mark.parametrize("act", ["silu", "gelu", "relu"])
def test_sidebar_gated_mlp_sweep(shape, act):
    m, d, f = shape
    x = _arr((m, d))
    wg, wu = _arr((d, f), scale=0.05), _arr((d, f), scale=0.05)
    wd = _arr((f, d), scale=0.05)
    got = ops.sidebar_gated_mlp(x, wg, wu, wd, act, interpret=True,
                                use_kernel=True)
    want = ref.sidebar_gated_mlp_ref(x, wg, wu, wd, act)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


def test_sidebar_gated_mlp_bf16():
    x = _arr((16, 128), jnp.bfloat16)
    wg, wu = _arr((128, 256), jnp.bfloat16, 0.05), _arr((128, 256), jnp.bfloat16, 0.05)
    wd = _arr((256, 128), jnp.bfloat16, 0.05)
    got = ops.sidebar_gated_mlp(x, wg, wu, wd, "silu", interpret=True,
                                use_kernel=True)
    want = ref.sidebar_gated_mlp_ref(x, wg, wu, wd, "silu")
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_sidebar_gated_mlp_table_swap():
    from repro.core.function_table import make_default_table
    table = make_default_table()
    table.register("swish2", lambda v: v * jax.nn.sigmoid(2.0 * v))
    x, wg = _arr((16, 128)), _arr((128, 256), scale=0.05)
    wu, wd = _arr((128, 256), scale=0.05), _arr((256, 128), scale=0.05)
    got = ops.sidebar_gated_mlp(x, wg, wu, wd, "swish2", table=table,
                                interpret=True, use_kernel=True)
    want = ref.sidebar_gated_mlp_ref(x, wg, wu, wd, "swish2", table)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)
