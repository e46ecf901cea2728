"""Fused gated (SwiGLU-family) Sidebar MLP: y = (f(x@Wg) ⊙ (x@Wu)) @ Wd.

The gated variant of ``sidebar_mlp`` — the hot pattern of 8 of the 10
assigned architectures (llama/deepseek/qwen/zamba/llama4/dsv3 experts).
TWO sidebar tiles live in VMEM scratch (gate and up paths); the flexible
function (from the host FunctionTable) and the elementwise gate product
run on the VPU between the MXU contractions; only y reaches HBM.

Tiling (BlockSpec):

  grid = (M/bm, F/bf), F minor (sequential accumulation axis).
  x       : (bm, D)  at (i, 0)
  wg, wu  : (D, bf)  at (0, j)
  wd      : (bf, D)  at (j, 0)
  out     : (bm, D)  at (i, 0)   — revisited across j (accumulate)
  scratch : sidebar_g (bm, bf) fp32, sidebar_u (bm, bf) fp32,
            acc (bm, D) fp32
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.function_table import DEFAULT_TABLE, FunctionTable
from repro.kernels.sidebar_mlp import plan_tiles

Array = jax.Array


def _kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, sb_g, sb_u, acc_ref, *,
            activation: Callable, n_f_blocks: int, out_dtype):
    j = pl.program_id(1)

    # static primitives #1/#2 (MXU): both halves into the sidebars
    sb_g[...] = jnp.dot(x_ref[...], wg_ref[...],
                        preferred_element_type=jnp.float32)
    sb_u[...] = jnp.dot(x_ref[...], wu_ref[...],
                        preferred_element_type=jnp.float32)

    # flexible function + gate product (VPU) on sidebar-resident tiles
    h = activation(sb_g[...]) * sb_u[...]

    # static primitive #3 (MXU): consume, accumulate
    part = jnp.dot(h.astype(wd_ref.dtype), wd_ref[...],
                   preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = part

    @pl.when(j > 0)
    def _accum():
        acc_ref[...] += part

    @pl.when(j == n_f_blocks - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def sidebar_gated_mlp(
    x: Array,
    w_gate: Array,
    w_up: Array,
    w_down: Array,
    activation: str | Callable = "silu",
    *,
    table: FunctionTable = DEFAULT_TABLE,
    interpret: bool = False,
) -> Array:
    m, d = x.shape
    _, f = w_gate.shape
    if w_up.shape != (d, f) or w_down.shape[0] != f:
        raise ValueError(
            f"shape mismatch: x{x.shape} wg{w_gate.shape} wu{w_up.shape} "
            f"wd{w_down.shape}"
        )
    d2 = w_down.shape[1]
    fn = table.lookup(activation) if isinstance(activation, str) else activation

    bm, bf = plan_tiles(m, d, f, d2, x.dtype.itemsize, up_panels=2,
                        sidebars=2)
    n_f_blocks = f // bf

    kernel = functools.partial(
        _kernel, activation=fn, n_f_blocks=n_f_blocks, out_dtype=x.dtype
    )
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n_f_blocks),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bf), lambda i, j: (0, j)),
            pl.BlockSpec((d, bf), lambda i, j: (0, j)),
            pl.BlockSpec((bf, d2), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, d2), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d2), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bf), jnp.float32),   # sidebar: gate path
            pltpu.VMEM((bm, bf), jnp.float32),   # sidebar: up path
            pltpu.VMEM((bm, d2), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(x, w_gate, w_up, w_down)
