"""Paged decode-attention kernels: the block table IS the DMA program.

The paged scheduler used to gather every request's KV blocks into a
dense slab per segment and scatter them back — a full-pool round-trip
per boundary, the exact DMA-dependent shape the paper shows losing to
in-place consumption. These kernels read the pool in place: the block
table and per-row lengths ride in SMEM (``PrefetchScalarGridSpec``), and
each grid step's ``index_map`` picks the *physical* block to DMA into
VMEM straight out of the table — so attention walks a request's logical
blocks wherever they physically live, with online-softmax accumulation
across the row's blocks (the flash_attention recurrence) in VMEM
scratch. No dense view is ever materialized.

Two families:

  * **GQA decode** (one query token per row): q (B, H, Dh) against
    pooled k/v (P, Hkv, bs, Dh), grid (B, Hkv, nb) with the block axis
    minor. int8-KV pools apply the pooled per-(position, head) scales
    in-kernel, to the logits and the probabilities — no dequantized
    block ever touches HBM.
  * **MLA absorbed decode**: q already projected into latent space
    (q_lat (B, H, kvr) fp32 + q_rope (B, H, rope)) against the pooled
    compressed cache (c_kv (P, bs, kvr), k_rope (P, bs, rope)), grid
    (B, nb); returns the latent context ctx_lat (B, H, kvr) fp32 — the
    w_uk/w_uv absorption stays outside (cheap, per-head-free matmuls).

The jnp references mirror ``models.attention``'s dense math op-for-op
(same einsums, fp32 accumulation, ``-1e30`` mask, ``jax.nn.softmax``):
they gather a dense view per layer through the table — narrower than
the retired pool-wide slab round-trip, and bit-identical to it wherever
the mask looks, because masked logits at ``-1e30`` underflow to exactly
0.0 in fp32, leaving softmax denominators and PV sums unchanged by any
junk behind the mask. The Pallas kernels accumulate online instead, so
they match the references to fp32 tolerance, not bitwise.

Tables must be validated in-bounds host-side before dispatch (the
scheduler's ``kvpool.validate_tables``): the reference gathers declare
``mode="promise_in_bounds"`` and the kernel's table-indexed DMA has no
bounds check at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Table gathers (the references' view builders). ``promise_in_bounds``:
# the scheduler validates tables host-side before every dispatch.
# ---------------------------------------------------------------------------


def _gather_kv(leaf: Array, tables: Array) -> Array:
    """(P, Hkv, bs, Dh) pool -> (B, Hkv, nb*bs, Dh) dense view."""
    g = leaf.at[tables].get(mode="promise_in_bounds")
    g = jnp.moveaxis(g, 1, 2)                     # (B, Hkv, nb, bs, Dh)
    return g.reshape(g.shape[0], g.shape[1], -1, leaf.shape[-1])


def _gather_scale(leaf: Array, tables: Array) -> Array:
    """(P, Hkv, bs) pooled scales -> (B, Hkv, nb*bs)."""
    g = leaf.at[tables].get(mode="promise_in_bounds")
    g = jnp.moveaxis(g, 1, 2)                     # (B, Hkv, nb, bs)
    return g.reshape(g.shape[0], g.shape[1], -1)


def _gather_lat(leaf: Array, tables: Array) -> Array:
    """(P, bs, r) pooled MLA latent/rope -> (B, nb*bs, r)."""
    g = leaf.at[tables].get(mode="promise_in_bounds")
    return g.reshape(g.shape[0], -1, leaf.shape[-1])


# ---------------------------------------------------------------------------
# References: the dense slab math, gathered through the table.
# ---------------------------------------------------------------------------


def paged_gqa_reference(
    q: Array,                     # (B, H, Dh) — one decode token per row
    k_pool: Array,                # (P, Hkv, bs, Dh)
    v_pool: Array,
    tables: Array,                # (B, nb) int32 physical block ids
    lengths: Array,               # (B,) int32 — row attends kpos < length
    *,
    scale: float,
    k_scale: Array | None = None,  # (P, Hkv, bs) fp32 int8-KV scales
    v_scale: Array | None = None,
    compute_dtype=None,
) -> Array:
    """Exactly ``models.attention._attend_direct_offset`` at s=1, fed by
    the table gather — the slab path's math, op for op."""
    b, h, dh = q.shape
    hkv = k_pool.shape[1]
    group = h // hkv
    dt = compute_dtype if compute_dtype is not None else q.dtype
    k = _gather_kv(k_pool, tables)
    v = _gather_kv(v_pool, tables)
    if k_scale is not None:
        k = (k.astype(jnp.float32)
             * _gather_scale(k_scale, tables)[..., None]).astype(dt)
        v = (v.astype(jnp.float32)
             * _gather_scale(v_scale, tables)[..., None]).astype(dt)
    else:
        k, v = k.astype(dt), v.astype(dt)
    t = k.shape[2]
    qg = q.reshape(b, hkv, group, 1, dh)
    logits = jnp.einsum("bhgsd,bhtd->bhgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(t)[None, :] <= (lengths - 1)[:, None]       # (B, t)
    logits = jnp.where(mask[:, None, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bhtd->bhgsd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, dh).astype(q.dtype)


def paged_mla_reference(
    q_lat: Array,                 # (B, H, kvr) fp32 — q @ absorbed w_uk
    q_rope: Array,                # (B, H, rope)
    ckv_pool: Array,              # (P, bs, kvr)
    krope_pool: Array,            # (P, bs, rope)
    tables: Array,                # (B, nb)
    lengths: Array,               # (B,)
    *,
    scale: float,
    compute_dtype=None,
) -> Array:
    """The MLA absorbed-decode logits/softmax/context, table-gathered;
    returns ctx_lat (B, H, kvr) fp32 (w_uv absorption stays outside)."""
    dt = compute_dtype if compute_dtype is not None else q_rope.dtype
    ckv = _gather_lat(ckv_pool, tables).astype(dt)        # (B, T, kvr)
    krope = _gather_lat(krope_pool, tables).astype(dt)    # (B, T, rope)
    t = ckv.shape[1]
    ql = q_lat[:, None]                                   # (B, 1, H, kvr)
    qr = q_rope[:, None].astype(jnp.float32)
    logits = (
        jnp.einsum("bshr,btr->bhst", ql, ckv.astype(jnp.float32))
        + jnp.einsum("bshn,btn->bhst", qr, krope.astype(jnp.float32))
    ) * scale
    end = (lengths - 1)[:, None, None, None]
    mask = jnp.arange(t)[None, None, None, :] <= end
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhst,btr->bshr", p, ckv.astype(jnp.float32))
    return ctx[:, 0]                                      # (B, H, kvr)


# ---------------------------------------------------------------------------
# GQA kernel: grid (B, Hkv, nb), block axis minor (online softmax).
# ---------------------------------------------------------------------------


def _gqa_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, *rest,
                scale: float, block_size: int, n_blocks: int,
                quantized: bool, out_dtype):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    hh = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]

    def head_row(ref):
        # the block holds every head's (Hkv, bs) scales (a (1, bs) block
        # would break the TPU's last-two-dims tiling); pick this head's
        # row as a (1, bs) lane vector with a masked sum
        sc = ref[0]
        rows = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
        return jnp.sum(jnp.where(rows == hh, sc, 0.0), axis=0,
                       keepdims=True)

    # blocks at or past the row's frontier hold junk (or another row's
    # data): skip them entirely — the causal mask in block form
    @pl.when(j * block_size < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)       # (group, Dh)
        k = k_ref[0, 0]                           # (bs, Dh) — this row's
        v = v_ref[0, 0]                           # table[b, j] pool block
        # static primitive #1 (MXU): q·K^T on the in-place pool block
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                 # (group, bs)
        if quantized:
            # int8 KV: the per-position scales factor out of the dot,
            # so they multiply the logits' and the probabilities' key
            # axis instead of dequantizing the block
            s = s * head_row(ks_ref)
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        # flexible step (VPU): online softmax across the row's blocks
        m_prev = m_ref[...]
        m_curr = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_curr)
        p = jnp.exp(s - m_curr)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        m_ref[...] = m_curr
        # static primitive #2 (MXU): weighted value accumulation
        if quantized:
            pv = jnp.dot(p * head_row(vs_ref), v.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        else:
            pv = jnp.dot(p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(j == n_blocks - 1)
    def _flush():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(out_dtype)


def paged_gqa_kernel(
    q: Array,
    k_pool: Array,
    v_pool: Array,
    tables: Array,
    lengths: Array,
    *,
    scale: float,
    k_scale: Array | None = None,
    v_scale: Array | None = None,
    interpret: bool = False,
) -> Array:
    """Paged GQA decode: q (B, H, Dh) against the pool in place."""
    b, h, dh = q.shape
    _, hkv, bs, _ = k_pool.shape
    if h % hkv:
        raise ValueError(f"GQA needs H % Hkv == 0, got {h} % {hkv}")
    group = h // hkv
    nb = tables.shape[1]
    qg = q.reshape(b, hkv, group, dh)
    quantized = k_scale is not None

    def q_map(bb, hh, jj, t, ln):
        return (bb, hh, 0, 0)

    def kv_map(bb, hh, jj, t, ln):
        # THE paged idiom: the physical block to DMA comes out of the
        # prefetched table, per grid step — no dense gather anywhere
        return (t[bb, jj], hh, 0, 0)

    def scale_map(bb, hh, jj, t, ln):
        return (t[bb, jj], 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, group, dh), q_map),
        pl.BlockSpec((1, 1, bs, dh), kv_map),
        pl.BlockSpec((1, 1, bs, dh), kv_map),
    ]
    args = [qg, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, hkv, bs), scale_map)] * 2
        args += [k_scale, v_scale]

    kernel = functools.partial(
        _gqa_kernel, scale=scale, block_size=bs, n_blocks=nb,
        quantized=quantized, out_dtype=q.dtype,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv, nb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, group, dh), q_map),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, dh), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), *args)
    return out.reshape(b, h, dh)


# ---------------------------------------------------------------------------
# MLA kernel: grid (B, nb) over the compressed pooled cache.
# ---------------------------------------------------------------------------


def _mla_kernel(tables_ref, lengths_ref, ql_ref, qr_ref, ckv_ref, kr_ref,
                o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                block_size: int, n_blocks: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]

    @pl.when(j * block_size < length)
    def _body():
        ql = ql_ref[0]                            # (H, kvr) fp32
        qr = qr_ref[0].astype(jnp.float32)        # (H, rope)
        ckv = ckv_ref[0].astype(jnp.float32)      # (bs, kvr) — table[b, j]
        kr = kr_ref[0].astype(jnp.float32)        # (bs, rope)
        # absorbed logits: latent + shared-rope contractions on the block
        s = (
            jax.lax.dot_general(ql, ckv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ) * scale                                 # (H, bs)
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_curr = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_curr)
        p = jnp.exp(s - m_curr)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        m_ref[...] = m_curr
        pv = jnp.dot(p, ckv, preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv  # (H, kvr)

    @pl.when(j == n_blocks - 1)
    def _flush():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = acc_ref[...] / l


def paged_mla_kernel(
    q_lat: Array,
    q_rope: Array,
    ckv_pool: Array,
    krope_pool: Array,
    tables: Array,
    lengths: Array,
    *,
    scale: float,
    interpret: bool = False,
) -> Array:
    """Paged MLA absorbed decode; returns ctx_lat (B, H, kvr) fp32."""
    b, h, kvr = q_lat.shape
    rope = q_rope.shape[-1]
    _, bs, _ = ckv_pool.shape
    nb = tables.shape[1]

    def q_map(bb, jj, t, ln):
        return (bb, 0, 0)

    def pool_map(bb, jj, t, ln):
        return (t[bb, jj], 0, 0)

    kernel = functools.partial(_mla_kernel, scale=scale, block_size=bs,
                               n_blocks=nb)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec((1, h, kvr), q_map),
                pl.BlockSpec((1, h, rope), q_map),
                pl.BlockSpec((1, bs, kvr), pool_map),
                pl.BlockSpec((1, bs, rope), pool_map),
            ],
            out_specs=pl.BlockSpec((1, h, kvr), q_map),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, kvr), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, kvr), jnp.float32),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q_lat.astype(jnp.float32), q_rope, ckv_pool, krope_pool)
