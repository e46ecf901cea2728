"""Paged-attention decode kernel: in-place pool decode, no slab copies.

PR-6's contract, layer by layer:

  * op level — the Pallas kernels (interpret mode on CPU) match the jnp
    references to fp32 tolerance on GQA fp32, GQA int8-KV (in-kernel
    dequant), and MLA absorbed decode, over ragged lengths, duplicate
    table entries, and scratch-padded tails.
  * scheduler level — ``kernel="paged"`` serves bit-exact tokens vs
    solo decode on every cache family while issuing ZERO pool-wide
    ``gather_blocks`` / ``scatter_blocks`` dispatches (the trace-time
    dispatch records are the observable); ``kernel="slab"`` keeps the
    gather/scatter reference segment, also bit-exact.
  * safety rails — out-of-table writes hit the drop sentinel instead of
    clamping onto a neighbour's last block; corrupt tables and
    span-overrunning segments raise ``KVPoolError`` host-side before
    any device dispatch could silently alias block 0.

Bit-exactness note: the jnp reference path (default config on CPU)
mirrors the slab attention op-for-op, so token equality is exact. The
Pallas kernels use an online softmax — the ``use_pallas`` end-to-end
smoke asserts drain/shape/dispatch, never exact tokens.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfglib
from repro.core.modes import ExecutionMode, ExecutionPlan, LayerPlan
from repro.kernels import ops as kops
from repro.kernels import paged_attention as pa
from repro.launch import kvpool as kvp
from repro.launch.scheduler import PagedContinuousBatchingServer
from repro.launch.serve import Server
from repro.models import attention as attn
from repro.models.registry import get_model

ARCHS = ["nemotron-4-15b", "nemotron-int8", "deepseek-v3-671b"]


def _cfg(arch: str):
    if arch == "nemotron-int8":
        cfg = dataclasses.replace(
            cfglib.get_smoke_config("nemotron-4-15b"),
            kv_cache_dtype=jnp.int8,
        )
    else:
        cfg = cfglib.get_smoke_config(arch)
    if cfg.num_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
    return cfg


@pytest.fixture(scope="module")
def served():
    out = {}
    for arch in ARCHS:
        cfg = _cfg(arch)
        api = get_model(cfg)
        params = api.init(jax.random.PRNGKey(0), cfg)
        out[arch] = (cfg, params, Server(cfg, params, max_len=48))
    return out


def _traffic(cfg, n, seed=0, max_prompt=14, max_gen=8):
    rng = np.random.RandomState(seed)
    return [
        (rng.randint(0, cfg.vocab_size, size=rng.randint(2, max_prompt))
         .astype(np.int32), int(rng.randint(1, max_gen + 1)))
        for _ in range(n)
    ]


def _check_exact(solo, done, reqs, arch=""):
    for r in done:
        prompt, gen = reqs[r.rid]
        assert r.generated == gen
        ref = solo.generate(jnp.asarray(prompt)[None, :], gen,
                            decode="loop")
        np.testing.assert_array_equal(
            np.asarray(ref.tokens)[0, prompt.size:], r.tokens,
            err_msg=f"{arch} rid {r.rid}: paged kernel != solo decode",
        )


# ---------------------------------------------------------------------------
# Op level: Pallas kernel (interpret) vs jnp reference
# ---------------------------------------------------------------------------


def _pools(rng, P, Hkv, bs, Dh, quantized):
    if quantized:
        k = jnp.asarray(rng.randint(-127, 128, (P, Hkv, bs, Dh))
                        .astype(np.int8))
        v = jnp.asarray(rng.randint(-127, 128, (P, Hkv, bs, Dh))
                        .astype(np.int8))
        ks = jnp.asarray((rng.rand(P, Hkv, bs).astype(np.float32) + .5)
                         / 127)
        vs = jnp.asarray((rng.rand(P, Hkv, bs).astype(np.float32) + .5)
                         / 127)
        return k, v, ks, vs
    k = jnp.asarray(rng.randn(P, Hkv, bs, Dh).astype(np.float32))
    v = jnp.asarray(rng.randn(P, Hkv, bs, Dh).astype(np.float32))
    return k, v, None, None


def _pool_problem(seed=0, *, quantized=False):
    """Random pool + tables with duplicate entries, a scratch-padded
    tail row, and ragged lengths (one mid-block, one block-aligned, one
    spanning the whole table)."""
    rng = np.random.RandomState(seed)
    P, Hkv, bs, Dh, B, nb, group = 9, 2, 8, 16, 3, 4, 4
    q = jnp.asarray(rng.randn(B, Hkv * group, Dh).astype(np.float32))
    tables = rng.randint(1, P, size=(B, nb)).astype(np.int32)
    tables[0, 1:] = kvp.SCRATCH_BLOCK          # short row, unused tail
    tables[1, 2] = tables[1, 1]                # duplicate (prefix-share)
    lengths = jnp.asarray(np.array([5, bs * 2, bs * nb], np.int32))
    k, v, ks, vs = _pools(rng, P, Hkv, bs, Dh, quantized)
    return q, k, v, ks, vs, jnp.asarray(tables), lengths, Dh ** -0.5


# The shapes the kernel's walk has to handle: (KV heads, group, block
# size, table width, blocks a chunk holds for fp32 pools, row lengths).
# int8 pools round a chunk up to whole 128-lane spans of scales, so
# there the first cases walk the table as one chunk.
_WALKS = {
    # Nemotron's grouping; lengths of 1, on a block boundary, on a chunk
    # boundary, mid-chunk and the whole table
    "group6": (8, 6, 8, 6, 2, [1, 8, 16, 21, 48]),
    # DeepSeek's: one query head per KV head; a table width (7) that is
    # not a multiple of the chunk (3), so the last chunk is short
    "group1": (16, 1, 8, 7, 3, [1, 24, 56, 13]),
    # rows far shorter than the table they are handed
    "short-rows": (2, 4, 8, 32, 4, [3, 256, 9]),
    # several chunks of 128 positions for int8 pools too
    "chunks": (2, 2, 16, 20, 8, [1, 128, 200, 320, 16]),
}


def _walk_problem(seed, hkv, group, bs, nb, lengths, *, quantized, dh=16):
    rng = np.random.RandomState(seed)
    b = len(lengths)
    P = b * nb + 1
    q = jnp.asarray(rng.randn(b, hkv * group, dh).astype(np.float32))
    # every row its own scattered blocks; block 0 is the scratch block
    tables = (rng.permutation(P - 1)[:b * nb] + 1).reshape(b, nb)
    k, v, ks, vs = _pools(rng, P, hkv, bs, dh, quantized)
    return (q, k, v, ks, vs, jnp.asarray(tables.astype(np.int32)),
            jnp.asarray(np.array(lengths, np.int32)), dh ** -0.5)


def _walk_pages(monkeypatch, case, quantized):
    """Size the kernel's chunk to the case's ``pages`` blocks of its
    pools (int8 rounds that up, see ``_WALKS``)."""
    hkv, _, bs, nb, pages, _ = _WALKS[case]
    block = hkv * bs * 16 * (1 if quantized else 4)
    monkeypatch.setattr(pa, "_CHUNK_BYTES", 2 * block * pages)
    if not quantized:
        assert pa._pages_per_chunk(block, bs, nb, quantized) == pages


@pytest.mark.parametrize("quantized, case", [
    pytest.param(quantized, case, id=kind + ("" if case is None
                                             else f"-{case}"))
    for case in (None, *_WALKS)
    for kind, quantized in (("fp32", False), ("int8", True))
])
def test_gqa_kernel_matches_reference(quantized, case, monkeypatch):
    if case is None:
        q, k, v, ks, vs, tables, lengths, scale = _pool_problem(
            seed=1, quantized=quantized)
    else:
        hkv, group, bs, nb, _, lengths = _WALKS[case]
        _walk_pages(monkeypatch, case, quantized)
        q, k, v, ks, vs, tables, lengths, scale = _walk_problem(
            1, hkv, group, bs, nb, lengths, quantized=quantized)
    ref = pa.paged_gqa_reference(q, k, v, tables, lengths, scale=scale,
                                 k_scale=ks, v_scale=vs)
    out = pa.paged_gqa_kernel(q, k, v, tables, lengths, scale=scale,
                              k_scale=ks, v_scale=vs, interpret=True)
    assert out.dtype == q.dtype and out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["fp32", "int8"])
def test_gqa_kernel_never_reads_past_the_frontier(quantized, monkeypatch):
    """Table entries past a row's frontier name blocks full of NaN (int8:
    NaN scales); the kernel's output stays finite and equal to the
    reference over the same table with those blocks clean."""
    case = "group1"
    hkv, group, bs, nb, _, lengths = _WALKS[case]
    _walk_pages(monkeypatch, case, quantized)
    q, k, v, ks, vs, tables, lengths, scale = _walk_problem(
        2, hkv, group, bs, nb, lengths, quantized=quantized)
    t = np.asarray(tables)
    live = -(-np.asarray(lengths) // bs)
    dead = np.concatenate([t[r, n:] for r, n in enumerate(live)])
    assert dead.size                           # the case has a tail
    ref = pa.paged_gqa_reference(q, k, v, tables, lengths, scale=scale,
                                 k_scale=ks, v_scale=vs)
    if quantized:
        ks, vs = (x.at[dead].set(jnp.nan) for x in (ks, vs))
    else:
        k, v = (x.at[dead].set(jnp.nan) for x in (k, v))
    out = pa.paged_gqa_kernel(q, k, v, tables, lengths, scale=scale,
                              k_scale=ks, v_scale=vs, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_mla_kernel_matches_reference():
    rng = np.random.RandomState(2)
    P, bs, B, nb, h, kvr, rope = 7, 8, 3, 4, 4, 32, 8
    ckv = jnp.asarray(rng.randn(P, bs, kvr).astype(np.float32))
    krope = jnp.asarray(rng.randn(P, bs, rope).astype(np.float32))
    ql = jnp.asarray(rng.randn(B, h, kvr).astype(np.float32))
    qr = jnp.asarray(rng.randn(B, h, rope).astype(np.float32))
    tables = rng.randint(1, P, size=(B, nb)).astype(np.int32)
    tables[2, 2:] = kvp.SCRATCH_BLOCK
    tables = jnp.asarray(tables)
    lengths = jnp.asarray(np.array([3, bs * nb, bs + 1], np.int32))
    scale = (kvr + rope) ** -0.5
    ref = pa.paged_mla_reference(ql, qr, ckv, krope, tables, lengths,
                                 scale=scale)
    out = pa.paged_mla_kernel(ql, qr, ckv, krope, tables, lengths,
                              scale=scale, interpret=True)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ops_dispatch_kernel_vs_reference_agree():
    """The ops-layer dispatch itself: forcing the kernel and forcing
    the reference agree, and each records its variant."""
    q, k, v, ks, vs, tables, lengths, scale = _pool_problem(seed=3)
    recs = []
    with kops.record_dispatches(recs):
        ref = kops.paged_attention_gqa(q, k, v, tables, lengths,
                                       scale=scale, use_kernel=False)
        out = kops.paged_attention_gqa(q, k, v, tables, lengths,
                                       scale=scale, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert [(r.op, r.variant, r.used_kernel) for r in recs] == [
        ("paged_attention", "ref", False),
        ("paged_attention", "paged", True),
    ]


# ---------------------------------------------------------------------------
# Scheduler level: in-place decode, zero slab copies, exact tokens
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCHS)
def test_paged_kernel_exact_and_no_slab_copies(arch, served):
    """The tentpole invariant: ``kernel="paged"`` decodes bit-exact vs
    solo on every cache family — prefix hits, ragged positions, fused
    admissions included — and its trace records show ZERO pool-wide
    gather/scatter, only table-walking paged attention."""
    cfg, params, solo = served[arch]
    recs = []
    with kops.record_dispatches(recs):
        sched = PagedContinuousBatchingServer(
            cfg, params, num_slots=3, max_len=48, block_size=8,
            prefill_chunk=8, segment=4, kernel="paged")
        reqs = _traffic(cfg, 6, seed=3)
        rids = [sched.submit(p, g) for p, g in reqs]
        done = sched.run()
    assert sorted(r.rid for r in done) == sorted(rids)
    _check_exact(solo, done, reqs, arch)
    ops_seen = {r.op for r in recs}
    assert "gather_blocks" not in ops_seen, ops_seen
    assert "scatter_blocks" not in ops_seen, ops_seen
    paged = [r for r in recs if r.op == "paged_attention"]
    assert paged, "paged segment never traced table-walking attention"
    # default config off-TPU routes to the jnp reference (exactness)
    assert all(r.variant == "ref" and not r.used_kernel for r in paged)
    # the executable cache keys carry the kernel choice + table width
    psegs = [k for k in sched.executable_cache_keys() if k[0] == "pseg"]
    assert psegs and all(k[6] == "paged" for k in psegs)
    assert all(1 <= k[7] <= sched.blocks_per_table for k in psegs)


def test_slab_kernel_keeps_gather_scatter_and_matches_paged(served):
    """``kernel="slab"`` preserves the reference segment — gathers and
    scatters recorded, tokens identical to the paged kernel's."""
    cfg, params, solo = served["nemotron-4-15b"]
    reqs = _traffic(cfg, 5, seed=7)

    def run(kernel):
        recs = []
        with kops.record_dispatches(recs):
            sched = PagedContinuousBatchingServer(
                cfg, params, num_slots=2, max_len=48, block_size=8,
                segment=4, kernel=kernel)
            for p, g in reqs:
                sched.submit(p, g)
            done = sched.run()
        return done, {r.op for r in recs}

    slab_done, slab_ops = run("slab")
    paged_done, _ = run("paged")
    assert "gather_blocks" in slab_ops and "scatter_blocks" in slab_ops
    assert "paged_attention" not in slab_ops
    _check_exact(solo, slab_done, reqs)
    for ra, rb in zip(slab_done, paged_done):
        assert ra.rid == rb.rid
        np.testing.assert_array_equal(ra.tokens, rb.tokens)


def test_unused_tail_table_entries_are_inert(served):
    """Short requests against a long max_len: most of every table row
    is scratch padding and the sliced segment width stays tiny — the
    dead entries never perturb tokens."""
    cfg, params, solo = served["nemotron-4-15b"]
    sched = PagedContinuousBatchingServer(
        cfg, params, num_slots=2, max_len=48, block_size=4, segment=4,
        kernel="paged")
    reqs = [(np.asarray([5, 3], np.int32), 3),
            (np.asarray([9], np.int32), 4)]
    for p, g in reqs:
        sched.submit(p, g)
    done = sched.run()
    _check_exact(solo, done, reqs)
    widths = {k[7] for k in sched.executable_cache_keys()
              if k[0] == "pseg"}
    assert widths and max(widths) < sched.blocks_per_table


def test_kernel_kwarg_validated(served):
    cfg, params, _ = served["nemotron-4-15b"]
    with pytest.raises(ValueError, match="kernel"):
        PagedContinuousBatchingServer(cfg, params, num_slots=1,
                                      max_len=32, block_size=8,
                                      kernel="dense")


# ---------------------------------------------------------------------------
# Safety rails: drop sentinel + host-side validation
# ---------------------------------------------------------------------------


def test_write_index_drops_out_of_table_positions():
    """Positions past the table map to the one-past-the-pool sentinel,
    so a ``mode="drop"`` scatter discards them — the old clamp aimed
    them at the row's LAST real block (cross-request corruption when
    the row was fully allocated)."""
    bs, nb, num_blocks = 4, 2, 6
    tables = jnp.asarray([[3, 5]], np.int32)
    pos = jnp.asarray([bs * nb - 1], jnp.int32)       # last in-table
    pb, off = attn._paged_write_index(tables, pos, 1, bs, num_blocks)
    assert int(pb[0]) == 5 and int(off[0]) == bs - 1
    pos = jnp.asarray([bs * nb], jnp.int32)           # first past it
    pb, off = attn._paged_write_index(tables, pos, 1, bs, num_blocks)
    assert int(pb[0]) == num_blocks                   # drop sentinel
    pool = jnp.zeros((num_blocks, bs))
    written = pool.at[pb, off].set(1.0, mode="drop")
    assert not np.asarray(written).any()              # pool untouched
    # a prefill chunk straddling the edge keeps its in-table writes
    pb, off = attn._paged_write_index(
        tables, jnp.int32(bs * nb - 2), 4, bs, num_blocks)
    assert np.asarray(pb)[0].tolist() == [5, 5, num_blocks, num_blocks]


def test_validate_tables_rejects_out_of_pool_entries():
    good = np.asarray([[0, 2, 1]], np.int32)
    kvp.validate_tables(good, num_blocks=3)
    for bad in ([[0, 3, 1]], [[0, -1, 1]]):
        with pytest.raises(kvp.KVPoolError, match="table"):
            kvp.validate_tables(np.asarray(bad, np.int32), num_blocks=3)


def test_check_span_rejects_frontier_overrun(served):
    cfg, params, _ = served["nemotron-4-15b"]
    sched = PagedContinuousBatchingServer(
        cfg, params, num_slots=1, max_len=32, block_size=8)
    rb = sched.mgr.begin_request(np.asarray([1, 2, 3], np.int32), 10)
    sched.mgr.check_span(rb, 10)                      # frontier == span ok
    with pytest.raises(kvp.KVPoolError, match="span"):
        sched.mgr.check_span(rb, 17)
    sched.mgr.release_request(rb)


# ---------------------------------------------------------------------------
# Pallas end-to-end (interpret) + per-layer plan dispatch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["nemotron-4-15b", "deepseek-v3-671b"])
def test_pallas_paged_kernel_serves_end_to_end(arch, served):
    """``use_pallas=True`` routes segment decode through the Pallas
    kernel (interpret mode off TPU) inside the scan-compiled segment:
    the server drains, per-request token counts are right, and the
    trace records confirm the kernel path ran. (No exact-token check:
    online softmax is tolerance-level, not bitwise.)"""
    cfg, params, _ = served[arch]
    cfg = dataclasses.replace(cfg, use_pallas=True)
    recs = []
    with kops.record_dispatches(recs):
        sched = PagedContinuousBatchingServer(
            cfg, params, num_slots=2, max_len=48, block_size=8,
            segment=4, kernel="paged")
        reqs = _traffic(cfg, 4, seed=11, max_gen=5)
        for p, g in reqs:
            sched.submit(p, g)
        done = sched.run()
    assert len(done) == len(reqs)
    for r in done:
        assert r.generated == reqs[r.rid][1]
        assert r.tokens.shape == (reqs[r.rid][1],)
    paged = [r for r in recs if r.op == "paged_attention"]
    assert paged and all(r.variant == "paged" and r.used_kernel
                         for r in paged)
    assert not {"gather_blocks", "scatter_blocks"} & {r.op for r in recs}


def test_flexible_dma_layer_takes_gather_route(served):
    """Per-layer plan dispatch reaches the paged op: a FLEXIBLE_DMA
    layer takes the dense-gather route (variant "dma"), sidebar layers
    the reference — and tokens stay exact vs solo under the same
    plan."""
    cfg, params, _ = served["nemotron-4-15b"]
    plan = ExecutionPlan(
        default=LayerPlan(ExecutionMode.SIDEBAR, 2),
        layers={1: LayerPlan(ExecutionMode.FLEXIBLE_DMA, 2)},
    )
    solo = Server(cfg, params, max_len=48, plan=plan)
    recs = []
    with kops.record_dispatches(recs):
        sched = PagedContinuousBatchingServer(
            cfg, params, num_slots=2, max_len=48, block_size=8,
            segment=4, plan=plan, kernel="paged")
        reqs = _traffic(cfg, 4, seed=13)
        for p, g in reqs:
            sched.submit(p, g)
        done = sched.run()
    _check_exact(solo, done, reqs)
    by_layer = {}
    for r in recs:
        if r.op == "paged_attention":
            by_layer.setdefault(r.layer, set()).add(r.variant)
    assert by_layer.get(1) == {"dma"}
    assert all(v == {"ref"} for k, v in by_layer.items() if k != 1)
    assert not {"gather_blocks", "scatter_blocks"} & {r.op for r in recs}
