"""Fused Sidebar MLP kernel: y = f(x @ W1) @ W2, one ``pallas_call``.

This is the TPU realization of the paper's SIDEBAR design for its hot
pattern (static matmul → flexible activation → static matmul):

  * The two matmuls are the *static* primitives — they run on the MXU.
  * The intermediate ``h = x @ W1`` tile lives in a **VMEM scratch buffer —
    the Sidebar**. It is never materialized to HBM, exists outside the
    program's array namespace (``scratch_shapes``), and holds intermediate
    data only — exactly the paper's scratchpad semantics.
  * The activation is the *flexible* function: it is looked up in the
    ``FunctionTable`` at trace time and applied to the sidebar tile on the
    VPU. Registering a new activation re-specializes the kernel with **no
    kernel-source change** — the software analogue of the paper's
    driver-provided host function.

Tiling (BlockSpec):

  grid = (M/bm, F/bf), F minor (sequential accumulation axis).
  x   : (bm, D)   block at (i, 0)      — row panel, K resident
  w1  : (D, bf)   block at (0, j)      — column panel of W1
  w2  : (bf, D)   block at (j, 0)      — row panel of W2
  out : (bm, D)   block at (i, 0)      — revisited for every j (accumulate)
  sidebar : VMEM (bm, bf) fp32         — the scratchpad
  acc     : VMEM (bm, D)  fp32         — output accumulator

VMEM (``vmem_bytes``): Pallas double-buffers every blocked operand (x,
the weight panels, out), so each counts twice; the scratch counts once,
and so does the f32 down-projection partial the compiler materialises
next to the accumulator. ``choose_tiles`` picks the largest (bm, bf)
whose total fits the compiler's default scoped-VMEM limit
(``constants.SCOPED_VMEM_BYTES``): at D = 6144 that is (8, 256) for
decode rows and (64, 128) for prefill rows.

The contraction dimension D stays resident (no k-blocking), so the
row tile and the weight panels shrink as D grows.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import constants
from repro.core.function_table import DEFAULT_TABLE, FunctionTable

Array = jax.Array


def vmem_bytes(bm: int, bf: int, d: int, d2: int, itemsize: int, *,
               up_panels: int = 1, sidebars: int = 1) -> int:
    """VMEM one fused-MLP ``pallas_call`` asks the compiler for.

    Blocked operands are double-buffered: the x row tile, ``up_panels``
    (D, bf) up-projection panels (2 for the gated kernel), the (bf, D2)
    down panel and the out tile. Scratch is single: ``sidebars`` fp32
    (bm, bf) tiles (the ring depth, or the gated kernel's two paths)
    and the fp32 accumulator, plus the fp32 partial of the second
    matmul, which the compiler keeps beside it.
    """
    blocked = bm * d + up_panels * d * bf + bf * d2 + bm * d2
    return 2 * itemsize * blocked + 4 * bm * bf * sidebars + 8 * bm * d2


def choose_tiles(m: int, d: int, f: int, itemsize: int = 2, *,
                 d2: int | None = None, up_panels: int = 1,
                 sidebars: int = 1) -> tuple[int, int] | None:
    """The largest (bm, bf) dividing (m, f) whose ``vmem_bytes`` fits
    ``constants.SCOPED_VMEM_BYTES``; None when no tiling fits (the op
    then takes its jnp reference, and records it)."""
    d2 = d if d2 is None else d2
    for bm in (256, 128, 64, 32, 16, 8):
        if bm > m or m % bm:
            continue
        for bf in (1024, 512, 256, 128):
            if bf > f or f % bf:
                continue
            need = vmem_bytes(bm, bf, d, d2, itemsize, up_panels=up_panels,
                              sidebars=sidebars)
            if need <= constants.SCOPED_VMEM_BYTES:
                return bm, bf
    return None


def plan_tiles(m: int, d: int, f: int, d2: int, itemsize: int,
               **kw) -> tuple[int, int]:
    """``choose_tiles`` for a kernel about to launch: raises when no
    tiling fits."""
    tiles = choose_tiles(m, d, f, itemsize, d2=d2, **kw)
    if tiles is None:
        raise ValueError(
            f"no (bm, bf) tiling of M={m} F={f} at D={d} fits "
            f"{constants.SCOPED_VMEM_BYTES} bytes of scoped VMEM")
    return tiles


def _kernel(x_ref, w1_ref, w2_ref, o_ref, sidebar_ref, acc_ref, *,
            activation: Callable, n_f_blocks: int, out_dtype):
    """One (i, j) grid step: sidebar tile j of row panel i."""
    j = pl.program_id(1)

    # --- static primitive #1 (MXU): partial intermediate into the sidebar.
    h = jnp.dot(x_ref[...], w1_ref[...], preferred_element_type=jnp.float32)
    sidebar_ref[...] = h

    # --- flexible function (VPU) on the sidebar-resident intermediate.
    #     `activation` was fetched from the FunctionTable at trace time:
    #     this is the host-function invocation of paper §3.3, with the
    #     handshake realized by program order inside the fused kernel.
    act = activation(sidebar_ref[...])

    # --- static primitive #2 (MXU): consume the sidebar, accumulate y.
    part = jnp.dot(
        act.astype(w2_ref.dtype), w2_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = part

    @pl.when(j > 0)
    def _accum():
        acc_ref[...] += part

    @pl.when(j == n_f_blocks - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def _pipelined_kernel(x_ref, w1_ref, w2_ref, o_ref, sidebar_ref, acc_ref, *,
                      activation: Callable, n_f_blocks: int, depth: int,
                      out_dtype):
    """One (i, j) step of the T-deep ring schedule, j in [0, n_f + T - 2].

    The sidebar is a ring ``(T, bm, bf)``; the producer of step j and the
    consumer of step j touch *different* slots (the consumer lags T-1
    steps), so there is no data dependence between them and the MXU
    matmul of the produce stage can overlap the VPU activation + MXU
    accumulate of the consume stage — the VMEM realization of the
    engine's per-region ownership trade. At T=2 (lag 1):

        j:       0          1              2         ...   n_f
        produce  h0 -> s0   h1 -> s1       h2 -> s0
        consume             f(s0) @ w2_0   f(s1) @ w2_1    f(.) @ w2_last

    The grid runs T-1 steps past the last f-block (the pipeline drain).
    """
    j = pl.program_id(1)
    lag = depth - 1

    @pl.when(j < n_f_blocks)
    def _produce():
        # static primitive #1 (MXU): fill this step's slot of the ring
        h = jnp.dot(
            x_ref[...], w1_ref[...], preferred_element_type=jnp.float32
        )
        sidebar_ref[j % depth] = h

    @pl.when(j >= lag)
    def _consume():
        # flexible function (VPU) + static primitive #2 (MXU) on the slot
        # filled T-1 steps ago — the oldest in-flight slot of the ring
        act = activation(sidebar_ref[(j - lag) % depth])
        part = jnp.dot(
            act.astype(w2_ref.dtype), w2_ref[...],
            preferred_element_type=jnp.float32,
        )

        @pl.when(j == lag)
        def _init():
            acc_ref[...] = part

        @pl.when(j > lag)
        def _accum():
            acc_ref[...] += part

    @pl.when(j == n_f_blocks + lag - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def sidebar_mlp(
    x: Array,
    w1: Array,
    w2: Array,
    activation: str | Callable = "relu",
    *,
    table: FunctionTable = DEFAULT_TABLE,
    interpret: bool = False,
) -> Array:
    """Fused f(x @ w1) @ w2 with the intermediate resident in VMEM.

    Args:
      x: (M, D) activations.
      w1: (D, F) up-projection.  w2: (F, D2) down-projection.
      activation: function-table key (or raw callable) — the flexible op.
      interpret: run the kernel body in Python on CPU (validation mode).
    """
    m, d = x.shape
    d1, f = w1.shape
    f2, d2 = w2.shape
    if d != d1 or f != f2:
        raise ValueError(f"shape mismatch: x{x.shape} w1{w1.shape} w2{w2.shape}")
    fn = table.lookup(activation) if isinstance(activation, str) else activation

    bm, bf = plan_tiles(m, d, f, d2, x.dtype.itemsize)
    n_f_blocks = f // bf

    grid = (m // bm, n_f_blocks)
    kernel = functools.partial(
        _kernel, activation=fn, n_f_blocks=n_f_blocks, out_dtype=x.dtype
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bf), lambda i, j: (0, j)),
            pl.BlockSpec((bf, d2), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, d2), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d2), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bf), jnp.float32),   # the Sidebar
            pltpu.VMEM((bm, d2), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(x, w1, w2)


def sidebar_mlp_pipelined(
    x: Array,
    w1: Array,
    w2: Array,
    activation: str | Callable = "relu",
    *,
    table: FunctionTable = DEFAULT_TABLE,
    depth: int = 2,
    interpret: bool = False,
) -> Array:
    """Ring-buffered f(x @ w1) @ w2: the sidebar is a ``depth``-deep VMEM
    ring and the f-axis grid is software-pipelined ``depth - 1`` steps
    deep, so the producer matmul of block j and the activation+consumer
    matmul of block j-(depth-1) are independent within every grid step
    (the kernel analogue of ExecutionMode.SIDEBAR_PIPELINED at ring depth
    T). ``depth=2`` is the PR-1 ping-pong pair; ``depth=1`` degenerates
    to the serial schedule. Numerically identical to ``sidebar_mlp`` at
    every depth.
    """
    m, d = x.shape
    d1, f = w1.shape
    f2, d2 = w2.shape
    if d != d1 or f != f2:
        raise ValueError(f"shape mismatch: x{x.shape} w1{w1.shape} w2{w2.shape}")
    if depth < 1:
        raise ValueError(f"ring depth must be >= 1, got {depth}")
    fn = table.lookup(activation) if isinstance(activation, str) else activation

    bm, bf = plan_tiles(m, d, f, d2, x.dtype.itemsize, sidebars=depth)
    n_f_blocks = f // bf
    lag = depth - 1

    # depth-1 drain steps past the last f-block; weight index maps clamp
    # so the warm-up/drain steps re-read a valid (ignored) panel
    grid = (m // bm, n_f_blocks + lag)
    last = n_f_blocks - 1
    kernel = functools.partial(
        _pipelined_kernel, activation=fn, n_f_blocks=n_f_blocks,
        depth=depth, out_dtype=x.dtype,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bf), lambda i, j: (0, jnp.minimum(j, last))),
            pl.BlockSpec(
                (bf, d2),
                lambda i, j: (jnp.clip(j - lag, 0, last), 0),
            ),
        ],
        out_specs=pl.BlockSpec((bm, d2), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d2), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((depth, bm, bf), jnp.float32),  # the Sidebar ring
            pltpu.VMEM((bm, d2), jnp.float32),         # output accumulator
        ],
        interpret=interpret,
    )(x, w1, w2)
