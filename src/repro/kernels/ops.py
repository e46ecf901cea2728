"""Jitted public entry points for the Pallas kernels.

Each op:
  * validates/normalizes shapes and dtypes,
  * dispatches to the Pallas kernel when shapes are TPU-tileable and the
    backend supports it, otherwise to the jnp oracle (bit-for-bit the same
    math) — so models can call these unconditionally,
  * is jit-friendly (static flags only via closure/partial).

``interpret`` is threaded through for CPU validation of the kernel bodies;
the models pass ``interpret_kernels()``, true on the CPU platform only.
The sidebar MLP and paged-attention ops record every dispatch, the jnp
fallback included (``record_dispatches``), so a run on the chip can
prove that its shapes took the kernels.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Callable

import jax

from repro.core.function_table import DEFAULT_TABLE, FunctionTable
from repro.core.modes import (
    ExecutionMode,
    ExecutionPlan,
    LayerPlan,
    coerce_layer_plan,
)
from repro.kernels import activations as _activations
from repro.kernels import ref
from repro.kernels.activations import activation as _activation_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.paged_attention import (
    paged_gqa_kernel as _paged_gqa_kernel,
    paged_gqa_reference as _paged_gqa_ref,
    paged_mla_kernel as _paged_mla_kernel,
    paged_mla_reference as _paged_mla_ref,
)
from repro.kernels.sidebar_gated_mlp import sidebar_gated_mlp as _gated_kernel
from repro.kernels.sidebar_matmul import sidebar_matmul as _matmul_kernel
from repro.kernels.sidebar_mlp import choose_tiles as _choose_mlp_tiles
from repro.kernels.sidebar_mlp import sidebar_mlp as _mlp_kernel
from repro.kernels.sidebar_mlp import (
    sidebar_mlp_pipelined as _mlp_kernel_pipelined,
)

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_kernels() -> bool:
    """Whether the models run their Pallas kernels in interpret mode:
    on the CPU platform only. On a TPU they compile for the chip, where
    a kernel the compiler refuses fails the program instead of falling
    back."""
    return jax.default_backend() == "cpu"


def _tileable(n: int, t: int = 128) -> bool:
    return n % t == 0


# -- execution-plan selection (wired from launch.serve.Server) -------------
# Models call the sidebar ops unconditionally; which kernel variant backs
# them (serial VMEM scratch vs T-deep ring pipelined, and how deep) is a
# deployment choice, so it is carried here as thread-local ambient state
# rather than threaded through every model signature. The ambient value
# may be a single ``LayerPlan`` (uniform) or a whole ``ExecutionPlan``:
# models announce which layer index they are tracing via ``layer_scope``
# and ``current_plan()`` resolves ``plan.for_layer(index)`` — so the
# planner's per-layer mode/depth choices reach each layer's kernel trace.

_PLAN_STATE = threading.local()

_DEFAULT_PLAN = LayerPlan(ExecutionMode.SIDEBAR, depth=1)


def current_layer() -> str | int | None:
    """The layer key the model is tracing right now (None outside any)."""
    return getattr(_PLAN_STATE, "layer", None)


@contextlib.contextmanager
def layer_scope(key: str | int | None):
    """Announce the layer being traced so a layer-indexed ``ExecutionPlan``
    resolves per-layer kernel variants. Models wrap each (unrolled) layer
    trace in this; a scanned stack traces once under the plan default."""
    prev = current_layer()
    _PLAN_STATE.layer = key
    try:
        yield
    finally:
        _PLAN_STATE.layer = prev


def current_full_plan() -> LayerPlan | ExecutionPlan:
    """The raw ambient plan (an ``ExecutionPlan`` stays layer-indexed)."""
    return getattr(_PLAN_STATE, "plan", _DEFAULT_PLAN)


def current_plan() -> LayerPlan:
    """The ``LayerPlan`` in effect for the layer currently being traced."""
    plan = current_full_plan()
    if isinstance(plan, ExecutionPlan):
        return plan.for_layer(current_layer())
    return plan


def current_execution_mode() -> ExecutionMode:
    return current_plan().mode


def set_plan(
    plan: LayerPlan | ExecutionPlan | ExecutionMode | str,
    depth: int | None = None,
) -> LayerPlan | ExecutionPlan:
    """Set the ambient sidebar kernel plan; returns the previous one.

    An ``ExecutionPlan`` is kept whole (layer-indexed resolution via
    ``layer_scope``); other spellings normalize to a ``LayerPlan``.
    """
    prev = current_full_plan()
    if isinstance(plan, ExecutionPlan):
        _PLAN_STATE.plan = plan
    else:
        _PLAN_STATE.plan = coerce_layer_plan(plan, depth)
    return prev


def set_execution_mode(
    mode: ExecutionMode | str, depth: int | None = None
) -> ExecutionMode:
    """Set the ambient sidebar kernel variant; returns the previous one."""
    prev = set_plan(mode, depth)
    if isinstance(prev, ExecutionPlan):
        return prev.default.mode
    return prev.mode


@contextlib.contextmanager
def execution_plan(
    plan: LayerPlan | ExecutionPlan | ExecutionMode | str,
    depth: int | None = None,
):
    prev = set_plan(plan, depth)
    try:
        yield
    finally:
        set_plan(prev)


@contextlib.contextmanager
def execution_mode(mode: ExecutionMode | str, depth: int | None = None):
    with execution_plan(mode, depth):
        yield


# -- dispatch recording (test/diagnostic probe) -----------------------------
# ``record_dispatches`` captures which kernel variant each sidebar op
# actually resolved at trace time — the observable for "the planner's
# per-layer choice reached the kernels" (a plan-state probe, cheaper and
# sharper than diffing HLO).


@dataclasses.dataclass(frozen=True)
class PlanDispatch:
    """One sidebar-op trace-time dispatch decision."""

    op: str                       # "sidebar_mlp" | "sidebar_gated_mlp" | ...
    layer: str | int | None       # ambient layer_scope key at trace time
    mode: ExecutionMode           # resolved plan mode
    depth: int                    # resolved ring depth
    variant: str                  # "pipelined" | "serial" | "dma" | "ref"
    used_kernel: bool             # the variant's primary kernel path was
    # taken: the fused Pallas kernel for serial/pipelined, the producer
    # matmul kernel for "dma" (its standalone host_activation gates its
    # own tiling independently and is not reflected here)


@contextlib.contextmanager
def record_dispatches(into: list):
    """Append a ``PlanDispatch`` per sidebar-op trace into ``into``."""
    prev = getattr(_PLAN_STATE, "recorder", None)
    _PLAN_STATE.recorder = into
    try:
        yield into
    finally:
        _PLAN_STATE.recorder = prev


def _record(op: str, mode: ExecutionMode, depth: int, variant: str,
            used_kernel: bool) -> None:
    rec = getattr(_PLAN_STATE, "recorder", None)
    if rec is not None:
        rec.append(PlanDispatch(op, current_layer(), mode, depth, variant,
                                used_kernel))


def record_dispatch(op: str, variant: str, used_kernel: bool = False) -> None:
    """Public trace-time dispatch record for non-sidebar hot-path ops
    (e.g. ``kvpool.gather_blocks`` — the observable that lets tests
    assert the paged-kernel segment issues ZERO pool-wide copies)."""
    plan = current_plan()
    _record(op, plan.mode, plan.depth, variant, used_kernel)


def sidebar_mlp(
    x: Array,
    w1: Array,
    w2: Array,
    activation: str | Callable = "relu",
    *,
    table: FunctionTable = DEFAULT_TABLE,
    use_kernel: bool | None = None,
    interpret: bool = False,
    pipelined: bool | None = None,
    depth: int | None = None,
) -> Array:
    """y = f(x @ w1) @ w2 — fused sidebar kernel when eligible.

    ``pipelined`` selects the T-deep ring variant and ``depth`` its ring
    depth; when None they follow the ambient ``execution_plan`` resolved
    for the layer currently being traced (``layer_scope``):
    SIDEBAR_PIPELINED => pipelined at the plan's depth, FLEXIBLE_DMA =>
    the unfused three-dispatch path (producer matmul, standalone host
    activation with the intermediate crossing HBM, consumer matmul),
    SIDEBAR / MONOLITHIC => the serial fused kernel. All variants are
    numerically equivalent.
    """
    m, d = x.shape
    _, f = w1.shape
    plan = current_plan()
    dma = (
        plan.mode is ExecutionMode.FLEXIBLE_DMA
        and pipelined is None
        and use_kernel is None
    )
    if pipelined is None:
        pipelined = plan.mode is ExecutionMode.SIDEBAR_PIPELINED
    if depth is None:
        if plan.mode is ExecutionMode.SIDEBAR_PIPELINED:
            depth = plan.depth  # the planner's scored choice, verbatim
        else:
            depth = 2 if pipelined else 1  # explicit opt-in: classic ring
    eligible = (
        _tileable(m, 8) and _tileable(f) and _tileable(d)
        and _choose_mlp_tiles(m, d, f, x.dtype.itemsize, d2=w2.shape[1],
                              sidebars=depth if pipelined else 1)
        is not None
    )
    use = (
        use_kernel
        if use_kernel is not None
        else (eligible and (_on_tpu() or interpret))
    )
    if dma:
        _record("sidebar_mlp", plan.mode, 1, "dma", use)
        h = sidebar_matmul(x, w1, "identity", table=table,
                           use_kernel=use_kernel, interpret=interpret)
        h = host_activation(h.astype(x.dtype), activation, table=table,
                            use_kernel=use_kernel, interpret=interpret)
        return sidebar_matmul(h.astype(x.dtype), w2, "identity", table=table,
                              use_kernel=use_kernel, interpret=interpret)
    if use:
        if pipelined:
            _record("sidebar_mlp", plan.mode, depth, "pipelined", True)
            return _mlp_kernel_pipelined(
                x, w1, w2, activation, table=table, depth=depth,
                interpret=interpret,
            )
        _record("sidebar_mlp", plan.mode, depth, "serial", True)
        return _mlp_kernel(x, w1, w2, activation, table=table,
                           interpret=interpret)
    _record("sidebar_mlp", plan.mode, depth, "ref", False)
    return ref.sidebar_mlp_ref(x, w1, w2, activation, table)


def sidebar_gated_mlp(
    x: Array,
    w_gate: Array,
    w_up: Array,
    w_down: Array,
    activation: str | Callable = "silu",
    *,
    table: FunctionTable = DEFAULT_TABLE,
    use_kernel: bool | None = None,
    interpret: bool = False,
) -> Array:
    """y = (f(x@Wg) * (x@Wu)) @ Wd — fused gated sidebar kernel."""
    m, d = x.shape
    _, f = w_gate.shape
    eligible = (
        _tileable(m, 8) and _tileable(f) and _tileable(d)
        and _choose_mlp_tiles(m, d, f, x.dtype.itemsize,
                              d2=w_down.shape[1], up_panels=2, sidebars=2)
        is not None
    )
    use = (
        use_kernel
        if use_kernel is not None
        else (eligible and (_on_tpu() or interpret))
    )
    plan = current_plan()
    if use:
        _record("sidebar_gated_mlp", plan.mode, 1, "serial", True)
        return _gated_kernel(x, w_gate, w_up, w_down, activation,
                             table=table, interpret=interpret)
    _record("sidebar_gated_mlp", plan.mode, 1, "ref", False)
    return ref.sidebar_gated_mlp_ref(x, w_gate, w_up, w_down, activation, table)


def sidebar_matmul(
    a: Array,
    b: Array,
    activation: str | Callable = "identity",
    *,
    table: FunctionTable = DEFAULT_TABLE,
    use_kernel: bool | None = None,
    interpret: bool = False,
) -> Array:
    m, k = a.shape
    _, n = b.shape
    eligible = _tileable(m, 8) and _tileable(n) and _tileable(k)
    use = (
        use_kernel
        if use_kernel is not None
        else (eligible and (_on_tpu() or interpret))
    )
    if use:
        return _matmul_kernel(a, b, activation, table=table, interpret=interpret)
    return ref.sidebar_matmul_ref(a, b, activation, table)


def host_activation(
    x: Array,
    activation: str | Callable = "relu",
    *,
    table: FunctionTable = DEFAULT_TABLE,
    use_kernel: bool | None = None,
    interpret: bool = False,
) -> Array:
    """The FLEXIBLE_DMA standalone host step (own launch, HBM round-trip).

    Eligibility is prechecked (``activations.tileable`` — the same block
    planning the kernel itself runs) like every other op here, instead of
    catching the kernel's shape ValueError: control flow stays exception-
    free and an explicit ``use_kernel=True`` on an untileable shape fails
    loudly instead of silently routing to the oracle.
    """
    eligible = x.ndim >= 1 and _activations.tileable(
        x.shape, activation, table=table
    )
    use = (
        use_kernel
        if use_kernel is not None
        else (eligible and (_on_tpu() or interpret))
    )
    if use:
        return _activation_kernel(x, activation, table=table,
                                  interpret=interpret)
    return ref.activation_ref(x, activation, table)


def flash_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    use_kernel: bool | None = None,
    interpret: bool = False,
    block_q: int = 128,
    block_k: int = 128,
) -> Array:
    b, hq, s, d = q.shape
    t = k.shape[2]
    eligible = (
        _tileable(min(s, block_q), 8)
        and s % min(block_q, s) == 0
        and t % min(block_k, t) == 0
    )
    use = (
        use_kernel
        if use_kernel is not None
        else (eligible and (_on_tpu() or interpret))
    )
    if use:
        return _flash_kernel(
            q, k, v, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
    return ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)


def paged_attention_gqa(
    q: Array,                     # (B, H, Dh) — single decode token/row
    k_pool: Array,                # (P, Hkv, bs, Dh) pooled blocks
    v_pool: Array,
    block_tables: Array,          # (B, nb) int32, host-validated in-bounds
    lengths: Array,               # (B,) int32 — row attends kpos < length
    *,
    scale: float,
    k_scale: Array | None = None,  # (P, Hkv, bs) fp32 int8-KV scales
    v_scale: Array | None = None,
    compute_dtype=None,
    use_kernel: bool | None = None,
    interpret: bool = False,
) -> Array:
    """Paged GQA decode attention, in place on the block pool.

    Dispatch mirrors the sidebar MLP ops: the Pallas kernel (table rows
    in SMEM, per-block DMA, online softmax) when eligible on TPU or
    under ``interpret``; otherwise the jnp reference — the slab path's
    dense math fed by a per-layer table gather, bit-identical to it. A
    layer planned ``FLEXIBLE_DMA`` also takes the gather route (the
    dense-view round-trip IS that mode's memory discipline), recorded as
    variant ``"dma"`` so per-layer plan choices stay observable.

    Under tensor-parallel serving this runs INSIDE the shard_map body,
    so ``h``/``hkv`` are per-shard locals (``H/tp``, ``Hkv/tp``) and the
    pool leaves are the shard's own head slice. Eligibility is decided
    on those locals — and since ``make_tp_spec`` only admits degrees
    dividing both head counts, the group size ``h // hkv`` (and hence
    kernel eligibility) is invariant across TP degrees: a config that
    takes the kernel solo takes it on every shard, with no collectives
    inside the kernel.
    """
    _, h, dh = q.shape
    _, hkv, bs, _ = k_pool.shape
    eligible = h % hkv == 0 and dh % 8 == 0 and bs % 4 == 0
    plan = current_plan()
    dma = plan.mode is ExecutionMode.FLEXIBLE_DMA and use_kernel is None
    use = (
        use_kernel
        if use_kernel is not None
        else (eligible and not dma and (_on_tpu() or interpret))
    )
    if use:
        _record("paged_attention", plan.mode, plan.depth, "paged", True)
        return _paged_gqa_kernel(
            q, k_pool, v_pool, block_tables, lengths, scale=scale,
            k_scale=k_scale, v_scale=v_scale, interpret=interpret,
        )
    _record("paged_attention", plan.mode, plan.depth,
            "dma" if dma else "ref", False)
    return _paged_gqa_ref(
        q, k_pool, v_pool, block_tables, lengths, scale=scale,
        k_scale=k_scale, v_scale=v_scale, compute_dtype=compute_dtype,
    )


def paged_attention_mla(
    q_lat: Array,                 # (B, H, kvr) fp32 — q @ absorbed w_uk
    q_rope: Array,                # (B, H, rope)
    ckv_pool: Array,              # (P, bs, kvr) pooled latent blocks
    krope_pool: Array,            # (P, bs, rope)
    block_tables: Array,
    lengths: Array,
    *,
    scale: float,
    compute_dtype=None,
    use_kernel: bool | None = None,
    interpret: bool = False,
) -> Array:
    """Paged MLA absorbed decode; returns ctx_lat (B, H, kvr) fp32.

    Same dispatch contract as ``paged_attention_gqa``; the w_uk
    projection (before) and w_uv absorption (after) stay with the model.
    Under TP only ``H`` is sharded (the latent pool replicates — it is
    head-free), so eligibility, decided on ``kvr``/``rope``/``bs``
    alone, is TP-degree-invariant by construction.
    """
    _, _, kvr = q_lat.shape
    rope = q_rope.shape[-1]
    bs = ckv_pool.shape[1]
    eligible = kvr % 8 == 0 and rope % 4 == 0 and bs % 4 == 0
    plan = current_plan()
    dma = plan.mode is ExecutionMode.FLEXIBLE_DMA and use_kernel is None
    use = (
        use_kernel
        if use_kernel is not None
        else (eligible and not dma and (_on_tpu() or interpret))
    )
    if use:
        _record("paged_attention", plan.mode, plan.depth, "paged", True)
        return _paged_mla_kernel(
            q_lat, q_rope, ckv_pool, krope_pool, block_tables, lengths,
            scale=scale, interpret=interpret,
        )
    _record("paged_attention", plan.mode, plan.depth,
            "dma" if dma else "ref", False)
    return _paged_mla_ref(
        q_lat, q_rope, ckv_pool, krope_pool, block_tables, lengths,
        scale=scale, compute_dtype=compute_dtype,
    )
