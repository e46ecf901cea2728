"""Attention blocks: GQA (+qk-norm, +cross-attn) and MLA (DeepSeek-V3).

Sidebar decomposition: the QKV/output projections and the two attention
einsums are *static* primitives (MXU); softmax and qk-RMSNorm are
*flexible* functions (VPU). In SIDEBAR mode the fused path is
``kernels/flash_attention.py`` (logits + softmax stats in VMEM scratch);
the XLA path below uses a chunked-scan formulation so long-sequence
prefill never materializes the full S×T logits (sub-quadratic memory).

KV caches:
  * GQA: (B, Hkv, T, Dh) per layer; optional int8 quantization with
    per-(token, head) scales (production decode memory trick).
  * MLA: compressed — (B, T, kv_lora_rank) latent + (B, T, rope_dim)
    shared rope key. Decode uses the absorbed-matmul formulation
    (q projected into latent space; no per-head K/V expansion).
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.parallel import tp
from repro.parallel.hints import constrain
from repro.models.layers import (
    MeshInfo,
    ParamSpec,
    _maybe,
    apply_rope,
    linear,
    rms_norm,
)

Array = jax.Array

CHUNK_Q = int(os.environ.get("REPRO_ATTN_CHUNK_Q", "1024"))  # q-block size (chunked XLA attention)


# ---------------------------------------------------------------------------
# Param specs.
# ---------------------------------------------------------------------------

def gqa_param_specs(cfg: ModelConfig, m: MeshInfo) -> dict:
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = cfg.dtype
    fsdp = tuple(m.fsdp) or None
    specs = {
        "wq": ParamSpec((d, h * dh), dt, _maybe(m, fsdp, "model")),
        "wk": ParamSpec((d, hkv * dh), dt, _maybe(m, fsdp, "model")),
        "wv": ParamSpec((d, hkv * dh), dt, _maybe(m, fsdp, "model")),
        "wo": ParamSpec((h * dh, d), dt, _maybe(m, "model", fsdp)),
    }
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((dh,), dt, P(None), "ones")
        specs["k_norm"] = ParamSpec((dh,), dt, P(None), "ones")
    return specs


def mla_param_specs(cfg: ModelConfig, m: MeshInfo) -> dict:
    d, h = cfg.d_model, cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vdh = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    dt = cfg.dtype
    fsdp = tuple(m.fsdp) or None
    return {
        "w_dq": ParamSpec((d, qr), dt, _maybe(m, fsdp, None)),
        "q_norm": ParamSpec((qr,), dt, P(None), "ones"),
        "w_uq": ParamSpec((qr, h * (nope + rope)), dt, _maybe(m, fsdp, "model")),
        "w_dkv": ParamSpec((d, kvr), dt, _maybe(m, fsdp, None)),
        "kv_norm": ParamSpec((kvr,), dt, P(None), "ones"),
        "w_kr": ParamSpec((d, rope), dt, _maybe(m, fsdp, None)),
        "w_uk": ParamSpec((kvr, h * nope), dt, _maybe(m, fsdp, "model")),
        "w_uv": ParamSpec((kvr, h * vdh), dt, _maybe(m, fsdp, "model")),
        "wo": ParamSpec((h * vdh, d), dt, _maybe(m, "model", fsdp)),
    }


def attn_param_specs(cfg: ModelConfig, m: MeshInfo) -> dict:
    return mla_param_specs(cfg, m) if cfg.use_mla else gqa_param_specs(cfg, m)


# ---------------------------------------------------------------------------
# KV cache (GQA).
# ---------------------------------------------------------------------------

def kv_cache_specs(cfg: ModelConfig, m: MeshInfo, batch: int, max_len: int,
                   num_layers: int) -> dict:
    """Stacked-over-layers cache specs (leading L dim, scan xs layout)."""
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    batch_ax = tuple(m.fsdp) or None
    if cfg.use_mla:
        return {
            "c_kv": ParamSpec((num_layers, batch, max_len, cfg.kv_lora_rank),
                              cfg.dtype, _maybe(m, None, batch_ax, None, None), "zeros"),
            "k_rope": ParamSpec((num_layers, batch, max_len, cfg.rope_head_dim),
                                cfg.dtype, _maybe(m, None, batch_ax, None, None), "zeros"),
        }
    kv_dt = cfg.kv_cache_dtype
    # GQA often has fewer kv heads than the TP degree (e.g. kv=8, TP=16).
    # Shard heads over "model" when divisible; else shard head_dim (the
    # QKV weights stay TP-sharded on the fused hkv*dh dim either way, and
    # XLA reconciles the two layouts with a local reshard).
    tp = m.size("model")
    if tp > 1 and hkv % tp != 0 and dh % tp == 0:
        head_ax, dh_ax = None, "model"
    else:
        head_ax, dh_ax = "model", None
    specs = {
        "k": ParamSpec((num_layers, batch, hkv, max_len, dh), kv_dt,
                       _maybe(m, None, batch_ax, head_ax, None, dh_ax), "zeros"),
        "v": ParamSpec((num_layers, batch, hkv, max_len, dh), kv_dt,
                       _maybe(m, None, batch_ax, head_ax, None, dh_ax), "zeros"),
    }
    if kv_dt == jnp.int8:
        specs["k_scale"] = ParamSpec((num_layers, batch, hkv, max_len), jnp.float32,
                                     _maybe(m, None, batch_ax, head_ax, None), "zeros")
        specs["v_scale"] = ParamSpec((num_layers, batch, hkv, max_len), jnp.float32,
                                     _maybe(m, None, batch_ax, head_ax, None), "zeros")
    return specs


def rowwise_pos(pos) -> bool:
    """True when ``cache_pos`` is a per-row ``(B,)`` vector — batched
    decode of slots sitting at unaligned positions (the continuous-
    batching scheduler's segment decode). Scalar positions keep the
    dense ``dynamic_update_slice`` fast path."""
    return pos is not None and getattr(pos, "ndim", 0) == 1


# ---------------------------------------------------------------------------
# Paged KV (block tables): gather/scatter between the pooled cache and
# the dense layout the attention math runs on.
# ---------------------------------------------------------------------------

# Block 0 is the reserved scratch block: free slots' block tables and the
# padded tail of every table point at it, so clamped/dead writes land in
# junk that no unmasked read ever sees (the same stale-KV-behind-the-
# causal-mask argument the slot scheduler already relies on).
SCRATCH_BLOCK = 0


def _paged_write_index(block_tables: Array, cache_pos, s: int, bs: int,
                       num_blocks: int):
    """Physical (block, offset) for each written token position.

    ``block_tables`` (B, nb) maps logical block j of each row onto a
    pooled block id. Positions are ``cache_pos`` (scalar or per-row
    ``(B,)``) plus the within-call token index. Returns ``(pb, off)``
    with shape ``(B,)`` for single-token decode and ``(B, s)`` for a
    prefill chunk — advanced-index scatters either way, so pooled
    writes cost one scatter exactly like the slot scheduler's rowwise
    path. A position past the table (a padded staging chunk running
    past it, or an idle row parked at ``max_len - 1`` under a sliced
    table) gets the out-of-range sentinel ``num_blocks`` — callers
    scatter with ``mode="drop"`` so the write vanishes instead of
    silently clamping onto the row's LAST real block (which corrupts a
    possibly prefix-shared neighbour when the table is fully
    allocated). The scheduler additionally span-checks real rows
    host-side before dispatch (``kvpool.PagedKVManager.check_span``).
    """
    b = block_tables.shape[0]
    nb = block_tables.shape[1]
    pos = jnp.asarray(cache_pos, jnp.int32)
    if s == 1:
        p = jnp.broadcast_to(pos, (b,)) if pos.ndim == 0 else pos  # (B,)
    else:
        start = pos[:, None] if pos.ndim == 1 else pos
        p = jnp.broadcast_to(start + jnp.arange(s, dtype=jnp.int32),
                             (b, s))
    blk = p // bs
    pb = jnp.take_along_axis(
        block_tables, jnp.minimum(blk, nb - 1).reshape(b, -1),
        axis=1).reshape(p.shape)
    # out-of-table positions -> one past the pool: a dead row that
    # mode="drop" scatters discard entirely
    pb = jnp.where(blk < nb, pb, num_blocks)
    return pb, p % bs


# Table gathers declare mode="promise_in_bounds" instead of jnp.take's
# default OOB *clipping*, which would silently read block 0 for any
# stale/corrupt table entry. The promise is real: tables are built from
# allocator-owned block ids padded with SCRATCH_BLOCK, and the scheduler
# re-validates host-side before every dispatch (kvpool.validate_tables).


def _paged_gather_kv(leaf: Array, block_tables: Array) -> Array:
    """(P, Hkv, bs, Dh) pooled KV -> (B, Hkv, nb*bs, Dh) dense view."""
    g = leaf.at[block_tables].get(
        mode="promise_in_bounds")        # (B, nb, Hkv, bs, Dh)
    g = jnp.moveaxis(g, 1, 2)                     # (B, Hkv, nb, bs, Dh)
    b, h = g.shape[0], g.shape[1]
    return g.reshape(b, h, -1, leaf.shape[-1])


def _paged_gather_scale(leaf: Array, block_tables: Array) -> Array:
    """(P, Hkv, bs) pooled scales -> (B, Hkv, nb*bs)."""
    g = leaf.at[block_tables].get(
        mode="promise_in_bounds")        # (B, nb, Hkv, bs)
    g = jnp.moveaxis(g, 1, 2)                     # (B, Hkv, nb, bs)
    return g.reshape(g.shape[0], g.shape[1], -1)


def _paged_gather_lat(leaf: Array, block_tables: Array) -> Array:
    """(P, bs, r) pooled MLA latent/rope -> (B, nb*bs, r)."""
    g = leaf.at[block_tables].get(
        mode="promise_in_bounds")        # (B, nb, bs, r)
    return g.reshape(g.shape[0], -1, leaf.shape[-1])


def _quantize_kv(x: Array) -> tuple[Array, Array]:
    """Per-(token, head) int8 quantization: x (B, Hkv, S, Dh)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)  # (B,Hkv,S)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None]).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q: Array, scale: Array, dtype) -> Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# Core attention math (XLA path): chunked over q blocks.
# ---------------------------------------------------------------------------

def _attend(q: Array, k: Array, v: Array, *, causal: bool, cfg: ModelConfig,
            offset: int | Array | None = None) -> Array:
    """q (B,H,S,Dh), k/v (B,Hkv,T,Dh). Chooses pallas / chunked / direct.

    ``offset`` is the global position of query row 0 (kpos <= qpos+offset
    is visible). Default (None) = queries at the sequence end (t - s).
    """
    b, h, s, dh = q.shape
    t = k.shape[2]
    if offset is None:
        offset = t - s
    static_end = isinstance(offset, int) and offset == t - s
    if cfg.use_pallas and s % 128 == 0 and t % 128 == 0 and static_end:
        return kops.flash_attention(q, k, v, causal=causal,
                                    interpret=kops.interpret_kernels())
    group = h // k.shape[1]
    scale = 1.0 / math.sqrt(dh)
    if s <= CHUNK_Q or s % CHUNK_Q:
        return _attend_direct(q, k, v, group, scale, causal, offset)
    return _attend_chunked(q, k, v, group, scale, causal, offset)


def _attend_direct(q, k, v, group, scale, causal, offset):
    return _attend_direct_offset(q, k, v, group, scale, causal, offset)


UNROLL_CHUNKS = int(os.environ.get("REPRO_ATTN_UNROLL", "64"))  # unroll threshold (causal skipping)


def _attend_chunked(q, k, v, group, scale, causal, offset):
    """Chunked over q: peak logits memory O(chunk x T), not O(S x T).

    When the chunk count is moderate the loop is UNROLLED with static
    k/v prefixes per chunk (chunk i attends k[: offset+(i+1)*CHUNK]) —
    the causal block-skipping that a scan cannot express (saves ~2x
    flops at s == t). Falls back to a scan for very long sequences.
    """
    b, h, s, dh = q.shape
    n_chunks = s // CHUNK_Q
    static_off = isinstance(offset, int)

    if causal and static_off and n_chunks <= UNROLL_CHUNKS:
        outs = []
        for i in range(n_chunks):
            qi = q[:, :, i * CHUNK_Q : (i + 1) * CHUNK_Q, :]
            qi = constrain(qi, ("batch", "model", None, None))
            end = offset + (i + 1) * CHUNK_Q
            ki, vi = k[:, :, :end, :], v[:, :, :end, :]
            outs.append(
                _attend_direct_offset(qi, ki, vi, group, scale, True,
                                      offset + i * CHUNK_Q)
            )
        return jnp.concatenate(outs, axis=2)

    qc = q.reshape(b, h, n_chunks, CHUNK_Q, dh).transpose(2, 0, 1, 3, 4)
    qc = constrain(qc, (None, "batch", "model", None, None))

    def body(carry, args):
        qi, idx = args
        qi = constrain(qi, ("batch", "model", None, None))
        out = _attend_direct_offset(qi, k, v, group, scale, causal,
                                    offset + idx * CHUNK_Q)
        return carry, out

    _, outs = jax.lax.scan(body, None, (qc, jnp.arange(n_chunks)))
    return outs.transpose(1, 2, 0, 3, 4).reshape(b, h, s, v.shape[-1])


def _attend_direct_offset(q, k, v, group, scale, causal, offset):
    b, h, s, dh = q.shape
    t = k.shape[2]
    qg = q.reshape(b, k.shape[1], group, s, dh)
    logits = jnp.einsum("bhgsd,bhtd->bhgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        if rowwise_pos(offset):
            # per-row query offsets (batched slots at unaligned
            # positions): (B, s, t) mask broadcast over (kv-head, group)
            qpos = jnp.arange(s)[None, :] + offset[:, None]           # (B, s)
            mask = jnp.arange(t)[None, None, :] <= qpos[:, :, None]   # (B, s, t)
            logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
        else:
            qpos = jnp.arange(s)[:, None] + offset
            kpos = jnp.arange(t)[None, :]
            logits = jnp.where(kpos <= qpos, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bhtd->bhgsd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, s, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA block.
# ---------------------------------------------------------------------------

def gqa_attention(
    params: dict,
    cfg: ModelConfig,
    x: Array,                      # (B, S, D)
    positions: Array,              # (B, S)
    *,
    causal: bool = True,
    cache: dict | None = None,     # per-layer slice (no leading L dim)
    cache_pos: Array | None = None,  # scalar write offset (decode/prefill)
    memory: Array | None = None,   # cross-attention memory (B, T, D)
    block_tables: Array | None = None,  # (B, nb) paged-KV mapping
) -> tuple[Array, dict | None]:
    b, s, d = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    q = linear(x, params["wq"]).reshape(b, s, h, dh)
    kv_src = memory if memory is not None else x
    k = linear(kv_src, params["wk"]).reshape(b, kv_src.shape[1], hkv, dh)
    v = linear(kv_src, params["wv"]).reshape(b, kv_src.shape[1], hkv, dh)

    if cfg.qk_norm:  # flexible op: qk-RMSNorm (qwen3)
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)

    if memory is None:  # rope only for self-attention
        q = apply_rope(q, positions, cfg.rope_theta)
        if cache is None:
            kpos = positions
        elif rowwise_pos(cache_pos):
            kpos = cache_pos[:, None] + jnp.arange(kv_src.shape[1])[None, :]
        else:
            kpos = cache_pos + jnp.arange(kv_src.shape[1])[None, :]
        k = apply_rope(k, kpos, cfg.rope_theta)

    q = constrain(q.transpose(0, 2, 1, 3), ("batch", "model", None, None))
    k = constrain(k.transpose(0, 2, 1, 3), ("batch", "model", None, None))
    v = constrain(v.transpose(0, 2, 1, 3), ("batch", "model", None, None))

    new_cache = None
    if cache is not None:
        int8 = cfg.kv_cache_dtype == jnp.int8
        if int8:
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
        else:
            kq, vq = k.astype(cfg.kv_cache_dtype), v.astype(cfg.kv_cache_dtype)
        new_cache = dict(cache)
        if block_tables is not None:
            # paged KV: the cache leaves are the pooled (P, Hkv, bs, Dh)
            # physical blocks; each written position scatters to its
            # row's table entry (blocks shared across rows by the prefix
            # cache are never in any row's write range — the scheduler's
            # copy-on-write guarantee).
            bs_blk = cache["k"].shape[2]
            num_blocks = cache["k"].shape[0]
            pb, po = _paged_write_index(block_tables, cache_pos, s, bs_blk,
                                        num_blocks)
            vals = {"k": kq, "v": vq}
            if int8:
                vals.update(k_scale=ks, v_scale=vs)
            if s == 1:
                # decode: one in-place dynamic_update_slice per row. A
                # (block, offset)-indexed scatter gets its own layout
                # from the TPU compiler, which then copies the whole
                # pool into it and each layer's slice back out for the
                # paged kernel, every step. A slice write keeps the
                # kernel's row-major layout. An out-of-table position
                # (the drop sentinel) writes the scratch block instead,
                # whose content no unmasked read ever sees.
                pb = jnp.where(pb < num_blocks, pb, SCRATCH_BLOCK)
                for name, val in vals.items():
                    leaf = cache[name]
                    for r in range(b):
                        row = val[r, :, 0]                 # (Hkv[, Dh])
                        row = row.reshape(1, row.shape[0], 1,
                                          *row.shape[1:])
                        start = (pb[r], 0, po[r]) + (0,) * (leaf.ndim - 3)
                        leaf = jax.lax.dynamic_update_slice(
                            leaf, row.astype(leaf.dtype), start)
                    new_cache[name] = leaf
            else:
                # mode="drop": out-of-table positions carry the OOB
                # sentinel block id and must vanish, never clamp onto a
                # real block
                for name, val in vals.items():
                    val = jnp.swapaxes(val, 1, 2)          # (B, s, Hkv...)
                    new_cache[name] = cache[name].at[pb, :, po].set(
                        val, mode="drop")
        elif rowwise_pos(cache_pos):
            # per-row scatter: slot row i writes its own position — ONE
            # batched program over unaligned slots instead of num_slots
            # vmapped batch-1 programs (the scheduler's segment decode).
            bidx = jnp.arange(b)
            if s == 1:
                new_cache["k"] = cache["k"].at[bidx, :, cache_pos, :].set(kq[:, :, 0, :])
                new_cache["v"] = cache["v"].at[bidx, :, cache_pos, :].set(vq[:, :, 0, :])
                if int8:
                    new_cache["k_scale"] = (
                        cache["k_scale"].at[bidx, :, cache_pos].set(ks[:, :, 0])
                    )
                    new_cache["v_scale"] = (
                        cache["v_scale"].at[bidx, :, cache_pos].set(vs[:, :, 0])
                    )
            else:
                # rowwise multi-token chunk on a dense slab (the draft
                # model's ingest program): each row writes s positions
                # from its own start; positions past the slab (padded
                # short rows) carry OOB indices and must vanish, not
                # clamp onto the slab's last column.
                ppos = cache_pos[:, None] + jnp.arange(s)[None, :]
                new_cache["k"] = cache["k"].at[bidx[:, None], :, ppos, :].set(
                    kq.transpose(0, 2, 1, 3), mode="drop")
                new_cache["v"] = cache["v"].at[bidx[:, None], :, ppos, :].set(
                    vq.transpose(0, 2, 1, 3), mode="drop")
                if int8:
                    new_cache["k_scale"] = (
                        cache["k_scale"].at[bidx[:, None], :, ppos].set(
                            ks.transpose(0, 2, 1), mode="drop"))
                    new_cache["v_scale"] = (
                        cache["v_scale"].at[bidx[:, None], :, ppos].set(
                            vs.transpose(0, 2, 1), mode="drop"))
        else:
            start = (0, 0, cache_pos, 0)
            new_cache["k"] = jax.lax.dynamic_update_slice(cache["k"], kq, start)
            new_cache["v"] = jax.lax.dynamic_update_slice(cache["v"], vq, start)
            if int8:
                new_cache["k_scale"] = jax.lax.dynamic_update_slice(
                    cache["k_scale"], ks, (0, 0, cache_pos))
                new_cache["v_scale"] = jax.lax.dynamic_update_slice(
                    cache["v_scale"], vs, (0, 0, cache_pos))
        if block_tables is not None and s == 1:
            # paged decode attention IN PLACE on the pool: the op walks
            # the block table directly (Pallas table-indexed DMA on
            # TPU / under interpret; a per-layer table gather feeding
            # the identical dense math in the jnp reference) — no
            # pool-wide slab view anywhere on the decode hot path.
            pos = jnp.asarray(cache_pos, jnp.int32)
            lengths = jnp.broadcast_to(pos, (b,)).astype(jnp.int32) + 1
            ctx = kops.paged_attention_gqa(
                q[:, :, 0, :], new_cache["k"], new_cache["v"],
                block_tables, lengths, scale=1.0 / math.sqrt(dh),
                k_scale=new_cache["k_scale"] if int8 else None,
                v_scale=new_cache["v_scale"] if int8 else None,
                compute_dtype=cfg.dtype,
                interpret=cfg.use_pallas and kops.interpret_kernels(),
            )
            out = ctx.reshape(b, 1, h * dh)
            return tp.psum_partial(linear(out, params["wo"])), new_cache
        if block_tables is not None:
            # prefill chunks (s > 1): dense (B, Hkv, nb*bs, Dh) view
            # gathered through the block table; junk in padded/unwritten
            # blocks sits behind the causal mask (exactly like a slab
            # cache's stale tail), so the attend below is bit-identical
            # to the slab path.
            kr = _paged_gather_kv(new_cache["k"], block_tables)
            vr = _paged_gather_kv(new_cache["v"], block_tables)
            if int8:
                k = _dequantize_kv(
                    kr, _paged_gather_scale(new_cache["k_scale"],
                                            block_tables), cfg.dtype)
                v = _dequantize_kv(
                    vr, _paged_gather_scale(new_cache["v_scale"],
                                            block_tables), cfg.dtype)
            else:
                k, v = kr.astype(cfg.dtype), vr.astype(cfg.dtype)
        elif int8:
            k = _dequantize_kv(new_cache["k"], new_cache["k_scale"], cfg.dtype)
            v = _dequantize_kv(new_cache["v"], new_cache["v_scale"], cfg.dtype)
        else:
            k = new_cache["k"].astype(cfg.dtype)
            v = new_cache["v"].astype(cfg.dtype)

    offset = cache_pos if cache is not None else None
    out = _attend(q, k, v, causal=causal and memory is None, cfg=cfg,
                  offset=offset)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * dh)
    return tp.psum_partial(linear(out, params["wo"])), new_cache


# ---------------------------------------------------------------------------
# MLA block (DeepSeek-V3).
# ---------------------------------------------------------------------------

def mla_attention(
    params: dict,
    cfg: ModelConfig,
    x: Array,
    positions: Array,
    *,
    cache: dict | None = None,
    cache_pos: Array | None = None,
    block_tables: Array | None = None,
) -> tuple[Array, dict | None]:
    b, s, d = x.shape
    h = cfg.num_heads
    nope, rope, vdh = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim

    # --- queries: low-rank down + norm (flexible) + up.
    c_q = linear(x, params["w_dq"])
    c_q = rms_norm(c_q, params["q_norm"], cfg.norm_eps)
    q = linear(c_q, params["w_uq"]).reshape(b, s, h, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    # --- compressed kv latent + shared rope key.
    c_kv = linear(x, params["w_dkv"])                     # (B,S,kvr)
    c_kv = rms_norm(c_kv, params["kv_norm"], cfg.norm_eps)
    k_rope = linear(x, params["w_kr"])                    # (B,S,rope)
    if cache is None:
        kpos = positions
    elif rowwise_pos(cache_pos):
        kpos = cache_pos[:, None] + jnp.arange(s)[None, :]
    else:
        kpos = cache_pos + jnp.arange(s)[None, :]
    k_rope = apply_rope(k_rope, kpos, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        new_cache = dict(cache)
        if block_tables is not None:
            # paged MLA: latent + rope leaves are (P, bs, r) pooled
            # blocks; the (block, offset) advanced-index scatter and the
            # table gather mirror the GQA path exactly.
            bs_blk = cache["c_kv"].shape[1]
            pb, po = _paged_write_index(block_tables, cache_pos, s, bs_blk,
                                        cache["c_kv"].shape[0])
            ckv_w = c_kv[:, 0, :] if s == 1 else c_kv
            kr_w = k_rope[:, 0, :] if s == 1 else k_rope
            new_cache["c_kv"] = cache["c_kv"].at[pb, po, :].set(
                ckv_w.astype(cache["c_kv"].dtype), mode="drop")
            new_cache["k_rope"] = cache["k_rope"].at[pb, po, :].set(
                kr_w.astype(cache["k_rope"].dtype), mode="drop")
            if s != 1:
                # prefill chunks attend through the dense gathered view;
                # single-token decode goes in place on the pool via
                # kops.paged_attention_mla in the absorbed branch below
                c_kv_full = _paged_gather_lat(
                    new_cache["c_kv"], block_tables).astype(cfg.dtype)
                k_rope_full = _paged_gather_lat(
                    new_cache["k_rope"], block_tables).astype(cfg.dtype)
        elif rowwise_pos(cache_pos):
            # per-row scatter (see gqa_attention): batched decode of
            # slots at unaligned positions; s > 1 is the rowwise chunk
            # write (draft-model ingest), OOB padded positions dropped.
            bidx = jnp.arange(b)
            if s == 1:
                new_cache["c_kv"] = cache["c_kv"].at[bidx, cache_pos, :].set(
                    c_kv[:, 0, :].astype(cache["c_kv"].dtype))
                new_cache["k_rope"] = cache["k_rope"].at[bidx, cache_pos, :].set(
                    k_rope[:, 0, :].astype(cache["k_rope"].dtype))
            else:
                ppos = cache_pos[:, None] + jnp.arange(s)[None, :]
                new_cache["c_kv"] = cache["c_kv"].at[bidx[:, None], ppos, :].set(
                    c_kv.astype(cache["c_kv"].dtype), mode="drop")
                new_cache["k_rope"] = cache["k_rope"].at[bidx[:, None], ppos, :].set(
                    k_rope.astype(cache["k_rope"].dtype), mode="drop")
        else:
            new_cache["c_kv"] = jax.lax.dynamic_update_slice(
                cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, cache_pos, 0))
            new_cache["k_rope"] = jax.lax.dynamic_update_slice(
                cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, cache_pos, 0))
        if block_tables is None:
            c_kv_full = new_cache["c_kv"].astype(cfg.dtype)
            k_rope_full = new_cache["k_rope"].astype(cfg.dtype)
    else:
        c_kv_full, k_rope_full = c_kv, k_rope

    scale = 1.0 / math.sqrt(nope + rope)

    if cache is not None and s == 1:
        # ---- absorbed decode: project q into latent space; never expand K/V.
        w_uk = params["w_uk"].reshape(cfg.kv_lora_rank, h, nope)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))        # (B,1,H,kvr)
        if block_tables is not None:
            # paged absorbed decode IN PLACE on the compressed pool —
            # same contract as the GQA route: Pallas table-indexed DMA
            # on TPU/interpret, per-layer table gather in the reference.
            pos = jnp.asarray(cache_pos, jnp.int32)
            lengths = jnp.broadcast_to(pos, (b,)).astype(jnp.int32) + 1
            ctx_lat = kops.paged_attention_mla(
                q_lat[:, 0], q_rope[:, 0], new_cache["c_kv"],
                new_cache["k_rope"], block_tables, lengths, scale=scale,
                compute_dtype=cfg.dtype,
                interpret=cfg.use_pallas and kops.interpret_kernels(),
            )[:, None]                                       # (B,1,H,kvr)
        else:
            t = c_kv_full.shape[1]
            logits = (
                jnp.einsum("bshr,btr->bhst", q_lat,
                           c_kv_full.astype(jnp.float32))
                + jnp.einsum("bshn,btn->bhst", q_rope.astype(jnp.float32),
                             k_rope_full.astype(jnp.float32))
            ) * scale
            end = cache_pos + s - 1            # scalar, or (B,) per-row
            if rowwise_pos(cache_pos):
                end = end[:, None, None, None]
            mask = jnp.arange(t)[None, None, None, :] <= end
            logits = jnp.where(mask, logits, -1e30)
            p = jax.nn.softmax(logits, axis=-1)              # flexible op
            ctx_lat = jnp.einsum("bhst,btr->bshr", p,
                                 c_kv_full.astype(jnp.float32))
        w_uv = params["w_uv"].reshape(cfg.kv_lora_rank, h, vdh)
        out = jnp.einsum("bshr,rhv->bshv", ctx_lat, w_uv.astype(jnp.float32))
        out = out.reshape(b, s, h * vdh).astype(cfg.dtype)
        return tp.psum_partial(linear(out, params["wo"])), new_cache

    t = c_kv_full.shape[1]

    # ---- train/prefill: expand per-head keys/values (naive MLA).
    k_nope = linear(c_kv_full, params["w_uk"]).reshape(b, t, h, nope)
    vv = linear(c_kv_full, params["w_uv"]).reshape(b, t, h, vdh)
    k_rope_b = jnp.broadcast_to(k_rope_full[:, :, None, :], (b, t, h, rope))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1).transpose(0, 2, 1, 3)
    k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1).transpose(0, 2, 1, 3)
    vv = vv.transpose(0, 2, 1, 3)
    offset = cache_pos if cache is not None else t - s
    # MLA head dims are non-uniform; always the XLA path.
    out = _attend_chunked(q_full, k_full, vv, 1, scale, True, offset) \
        if s > CHUNK_Q and s % CHUNK_Q == 0 else \
        _attend_direct(q_full, k_full, vv, 1, scale, True, offset)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * vdh)
    return tp.psum_partial(linear(out, params["wo"])), new_cache


def attention(params, cfg, x, positions, **kw):
    if cfg.use_mla:
        kw.pop("memory", None)
        kw.pop("causal", None)
        return mla_attention(params, cfg, x, positions, **kw)
    return gqa_attention(params, cfg, x, positions, **kw)
