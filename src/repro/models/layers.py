"""Shared building blocks: param specs, norms, rope, embeddings, linears.

Parameters are plain nested dicts. Every leaf is declared as a ``ParamSpec``
(shape, dtype, PartitionSpec) so the same tree drives:

  * ``materialize``  — RNG init for smoke tests / real training,
  * ``abstract``     — ShapeDtypeStruct stand-ins for the dry-run
                        (no allocation at 405B scale),
  * ``shardings``    — NamedSharding tree for pjit in_shardings.

Sharding convention (see DESIGN.md §5): ``fsdp`` axes = ("pod","data")
when present — parameters are sharded over them and all-gathered by the
XLA SPMD partitioner at use (ZeRO-3); "model" is Megatron-style TP.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array


class ParamSpec(NamedTuple):
    shape: tuple[int, ...]
    dtype: Any
    pspec: P  # PartitionSpec over the production mesh axes
    init: str = "normal"  # "normal" | "zeros" | "ones" | "embed"


@dataclasses.dataclass(frozen=True)
class MeshInfo:
    """Which mesh axes exist, how they're used, and how big they are."""

    axis_names: tuple[str, ...]
    fsdp: tuple[str, ...]   # parameter/optimizer sharding axes ("pod","data")
    tp: str = "model"       # tensor-parallel axis
    sizes: tuple[tuple[str, int], ...] = ()

    @classmethod
    def from_axes(cls, axis_names: tuple[str, ...],
                  sizes: dict[str, int] | None = None) -> "MeshInfo":
        fsdp = tuple(a for a in ("pod", "data") if a in axis_names)
        size_map = tuple(sorted((sizes or {}).items()))
        return cls(tuple(axis_names), fsdp, sizes=size_map)

    def size(self, axes) -> int:
        """Product of the sizes of `axes` (1 for unknown axes)."""
        m = dict(self.sizes)
        if axes is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        n = 1
        for a in axes:
            n *= m.get(a, 1)
        return n

    @property
    def batch_axes(self) -> tuple[str, ...]:
        return self.fsdp  # data parallel over the same axes


SINGLE_POD = MeshInfo.from_axes(("data", "model"))
MULTI_POD = MeshInfo.from_axes(("pod", "data", "model"))
HOST = MeshInfo.from_axes(())  # single-device smoke tests: fully replicated


def _maybe(minfo: MeshInfo, *axes):
    """Build a PartitionSpec entry, dropping axes absent from the mesh."""
    out = []
    for a in axes:
        if a is None:
            out.append(None)
        elif isinstance(a, tuple):
            present = tuple(x for x in a if x in minfo.axis_names)
            out.append(present if present else None)
        else:
            out.append(a if a in minfo.axis_names else None)
    return P(*out)


# ---------------------------------------------------------------------------
# Tree utilities.
# ---------------------------------------------------------------------------

def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def materialize(key: Array, tree, scale_override: float | None = None,
                shardings=None):
    """Initialize every ParamSpec leaf with its declared initializer.

    One jitted program makes every leaf on the device, in its own dtype
    and (with ``shardings``, a matching tree) already partitioned, so
    no full-size float32 or unsharded copy of a leaf ever exists
    outside it.
    """
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_spec)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            _init_leaf(k, spec, scale_override)
            for k, spec in zip(keys, leaves)])

    return jax.jit(make, out_shardings=shardings)(key)


def _init_leaf(k: Array, spec: ParamSpec, scale_override: float | None):
    # second-to-last dim is the contraction (fan-in) dim; leading dims
    # are layer-stack / expert dims and must not affect the scale.
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else max(spec.shape[-1], 1)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "embed":
        return (jax.random.normal(k, spec.shape, jnp.float32) * 0.02).astype(spec.dtype)
    std = scale_override or (1.0 / math.sqrt(fan_in))
    return (jax.random.normal(k, spec.shape, jnp.float32) * std).astype(spec.dtype)


def abstract(tree):
    """ShapeDtypeStruct stand-ins (dry-run: no device allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), tree, is_leaf=is_spec
    )


def sanitize_pspec(mesh: Mesh, pspec: P, shape: tuple[int, ...]) -> P:
    """Drop axis assignments whose dimension isn't divisible by the axis
    size on this mesh (e.g. batch=1 on a 16-way data axis). The safety
    net behind every explicit in_sharding."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    entries = list(pspec) + [None] * (len(shape) - len(pspec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            n *= sizes.get(a, 1)
        out.append(entry if (n and dim % n == 0) else None)
    return P(*out)


def shardings(mesh: Mesh, tree):
    """NamedSharding tree matching the ParamSpec tree (divisibility-safe)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, sanitize_pspec(mesh, s.pspec, s.shape)),
        tree, is_leaf=is_spec,
    )


def param_bytes(tree) -> int:
    leaves = jax.tree.leaves(tree, is_leaf=is_spec)
    return sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize for s in leaves)


def param_count(tree) -> int:
    leaves = jax.tree.leaves(tree, is_leaf=is_spec)
    return sum(math.prod(s.shape) for s in leaves)


def stack_specs(tree, n: int, axis_name=None):
    """Stack a per-layer spec tree n times (scan-over-layers layout).

    The leading (layer) dimension is never sharded.
    """
    def stack(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n, *s.shape), s.dtype, P(None, *s.pspec), s.init)

    return jax.tree.map(stack, tree, is_leaf=is_spec)


# ---------------------------------------------------------------------------
# Numerics.
# ---------------------------------------------------------------------------

def rms_norm(x: Array, weight: Array, eps: float = 1e-6) -> Array:
    """RMSNorm — a *flexible* op (rowwise) in the sidebar decomposition."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: Array, weight: Array, bias: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float = 10000.0) -> Array:
    """Rotary embedding. x: (..., S, H, Dh) or (..., S, Dh); positions (..., S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                      # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    if x.ndim == angles.ndim + 1:                      # head dim present
        angles = angles[..., None, :]                  # (..., S, 1, Dh/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., ::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def linear(x: Array, w: Array) -> Array:
    """x (..., D) @ w (D, F) — a *static* primitive.

    bf16 inputs keep a bf16 dot OUTPUT (the MXU still accumulates fp32
    internally): under tensor parallelism the partial-sum all-reduce then
    moves bf16, not fp32 — this halved the TP collective bytes on the
    llama3-405b train cell (EXPERIMENTS.md §Perf iteration 4). fp32
    inputs keep explicit fp32 accumulation.
    """
    if x.dtype == jnp.float32:
        return jnp.dot(x, w, preferred_element_type=jnp.float32)
    return jnp.dot(x, w, preferred_element_type=x.dtype)


VOCAB_PAD = 16  # embeddings padded so the vocab dim shards over "model"


def padded_vocab(v: int) -> int:
    """Megatron-style vocab padding to the TP degree (16 on both meshes)."""
    return -(-v // VOCAB_PAD) * VOCAB_PAD


def embed_lookup(table: Array, tokens: Array, *, sharded: bool = False) -> Array:
    """Embedding lookup.

    Sharded (vocab-parallel) path: one-hot einsum — the SPMD partitioner
    turns it into a local contraction + psum instead of the 'involuntary
    full rematerialization' (whole-table all-gather) a sharded gather
    triggers. Unsharded path: plain take().

    Under ambient TP (manual shard_map) the local table holds vocab rows
    [offset, offset + V_local): ids are rebased, off-shard ids one-hot to
    all-zero rows, and the fp32 partials are psum'd BEFORE the dtype
    cast — summing exact zeros with one exact row keeps the lookup
    bit-identical to the unsharded take() at any shard count.
    """
    if not sharded:
        return jnp.take(table, tokens, axis=0)
    from repro.parallel import tp

    ids = tokens - tp.shard_offset(table.shape[0]) \
        if tp.active() else tokens
    onehot = jax.nn.one_hot(ids, table.shape[0], dtype=table.dtype)
    out = jnp.einsum("...v,vd->...d", onehot, table,
                     preferred_element_type=jnp.float32)
    return tp.psum_partial(out).astype(table.dtype)


def unembed(x: Array, table: Array) -> Array:
    """Logits = x @ E^T (tied); fp32 out; width = padded vocab.

    Under ambient TP the table holds a vocab-row shard, so the local dot
    yields a column slice of the logits — exact per column, the
    contraction dim d is never split — which the tiled all-gather
    reassembles to full width once per step (identity outside TP).
    """
    from repro.parallel import tp

    return tp.all_gather_cols(
        jnp.dot(x, table.T, preferred_element_type=jnp.float32))


def mask_pad_logits(logits: Array, vocab: int) -> Array:
    """-inf the padded vocab columns (zero-init rows would otherwise bias
    softmax mass / argmax)."""
    if logits.shape[-1] == vocab:
        return logits
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return jnp.where(cols < vocab, logits, -1e30)


def softmax_cross_entropy(logits: Array, labels: Array,
                          vocab: int | None = None) -> Array:
    """Mean token NLL; logits fp32 (T, V_pad), labels int (T,)."""
    logits = logits.astype(jnp.float32)
    if vocab is not None:
        logits = mask_pad_logits(logits, vocab)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
