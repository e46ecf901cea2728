"""Preemption, priority scheduling, and spill/restore: bit-exact under
overload.

The tentpole invariant extends the paged scheduler's: a request that is
preempted mid-generation — its KV spilled to the host-side sidebar
region, its blocks released, later restored and resumed — produces
EXACTLY the tokens an unpreempted solo decode produces, greedy and
sampled alike, on the GQA, int8-KV, and MLA+MoE cache families. Spill
is a full copy + full release (a spilled request pins zero pool
memory), restore re-splices what the prefix index still holds and
rewrites the rest, and the position-keyed PRNG makes a sampled stream a
pure function of (seed, position) — restart-safe by construction.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfglib
from repro.core.sidebar import SidebarSpillRegion
from repro.launch.sampling import SamplingParams
from repro.launch.scheduler import (
    ContinuousBatchingServer,
    PagedContinuousBatchingServer,
)
from repro.launch.serve import Server
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
        cfg = dataclasses.replace(cfg,
                                  capacity_factor=float(cfg.num_experts))
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


def _check_exact(solo, done, reqs, samples=None, arch=""):
    for r in done:
        prompt, gen = reqs[r.rid]
        sample = None if samples is None else samples.get(r.rid)
        assert r.generated == gen
        ref = solo.generate(jnp.asarray(prompt)[None, :], gen,
                            decode="loop", sample=sample)
        np.testing.assert_array_equal(
            np.asarray(ref.tokens)[0, prompt.size:], r.tokens,
            err_msg=f"{arch} rid {r.rid}: preempted != solo decode",
        )


def _assert_quiescent(sched):
    assert sched.mgr.alloc.in_use == 0
    assert (sched.mgr.alloc.num_free + sched.mgr.alloc.num_evictable
            == sched.mgr.alloc.capacity)
    assert len(sched.spill) == 0
    assert sched.spill.in_use_bytes == 0


def _tight_server(cfg, params, **kw):
    """A pool sized so two fully grown requests cannot coexist: lazy
    growth hits the wall mid-generation and the worse-scored request
    self-spills (no strictly worse victim exists) — deterministic
    preemption without any fault injection."""
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 6)      # 5 allocatable < 2 * 3-block spans
    kw.setdefault("segment", 4)
    return PagedContinuousBatchingServer(cfg, params, **kw)


def _tight_traffic(cfg, n=2, seed=3):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, cfg.vocab_size, size=6).astype(np.int32), 18)
            for _ in range(n)]          # span 23 pos -> 3 blocks each


# ---------------------------------------------------------------------------
# Tentpole: preempt -> spill -> restore is invisible in the tokens.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ARCHS)
def test_preempt_restore_bitexact_greedy(arch, served):
    cfg, params, solo = served[arch]
    sched = _tight_server(cfg, params)
    reqs = _tight_traffic(cfg)
    for p, g in reqs:
        sched.submit(p, g)
    done = sched.run()
    assert len(done) == len(reqs)
    assert sched.stats.preemptions > 0, "pool was not tight enough"
    assert sched.stats.restores > 0
    _check_exact(solo, done, reqs, arch=arch)
    _assert_quiescent(sched)


@pytest.mark.parametrize("arch", ARCHS)
def test_preempt_restore_bitexact_sampled(arch, served):
    """The position-keyed PRNG makes the sampled stream restart-safe:
    the restored request re-derives exactly the draws it would have
    made uninterrupted."""
    cfg, params, solo = served[arch]
    sched = _tight_server(cfg, params)
    reqs = _tight_traffic(cfg)
    sp = SamplingParams(temperature=0.8, top_k=40, seed=13)
    samples = {0: None, 1: sp}          # the later (victim) one samples
    for rid, (p, g) in enumerate(reqs):
        sched.submit(p, g, sample=samples[rid])
    done = sched.run()
    assert len(done) == len(reqs)
    assert sched.stats.preemptions > 0
    _check_exact(solo, done, reqs, samples=samples, arch=arch)
    _assert_quiescent(sched)


def test_lazy_growth_allocates_segment_by_segment(served):
    """Staging takes only the prompt's blocks; the full span shows up
    segment by segment — the whole point of lazy allocation (eager
    reservation is what made overload admission all-or-nothing)."""
    cfg, params, _ = served["nemotron-4-15b"]
    sched = PagedContinuousBatchingServer(
        cfg, params, num_slots=1, max_len=48, block_size=8, segment=4)
    prompt = np.arange(1, 7, dtype=np.int32)        # S=6
    sched.submit(prompt, 20)                        # span 25 -> 4 blocks
    full = sched.mgr.blocks_needed(prompt.size + 20 - 1)
    sched.step()
    rb = sched._slot_rb[0]
    assert rb is not None
    grown_early = len(rb.bids)
    assert grown_early < full, (
        f"first segment already owns the full span "
        f"({grown_early}/{full} blocks) — allocation is not lazy")
    sched.run()
    assert sched.stats.preemptions == 0             # growth never failed


def test_spill_region_accounting(served):
    cfg, params, _ = served["nemotron-4-15b"]
    region = SidebarSpillRegion()
    sched = _tight_server(cfg, params, spill_region=region)
    for p, g in _tight_traffic(cfg):
        sched.submit(p, g)
    done = sched.run()
    assert len(done) == 2
    assert region.spills == sched.stats.preemptions > 0
    assert region.restores > 0
    assert region.peak_bytes > 0
    assert region.in_use_bytes == 0 and len(region) == 0


def test_eviction_storm_while_spilled_never_breaks_restore(served):
    """The satellite: force-evict EVERY cached block while a request
    sits spilled — restore must rewrite from host copies instead of
    splicing, bit-exactly. (Spill releases all refcounts precisely so
    no eviction can ever be unsafe for a spilled request.)"""
    cfg, params, solo = served["nemotron-4-15b"]
    sched = _tight_server(cfg, params)
    reqs = _tight_traffic(cfg)
    for p, g in reqs:
        sched.submit(p, g)
    done = []
    stormed = False
    while sched._has_work():
        done.extend(sched.step(draining=True))
        if sched._spilled and not stormed:
            stormed = True
            sched.mgr.alloc.evict_cached()          # flush the index
            assert sched.mgr.alloc.num_evictable == 0
    assert stormed, "no spill happened — pool was not tight enough"
    assert len(done) == len(reqs)
    assert sched.stats.restores > 0
    _check_exact(solo, done, reqs)
    _assert_quiescent(sched)


# ---------------------------------------------------------------------------
# Priority classes + EDF admission.
# ---------------------------------------------------------------------------

def test_priority_jumps_the_queue(served):
    """With one slot and a low-priority backlog, a late high-priority
    arrival is staged and admitted ahead of every queued request (but
    behind the one already decoding — admission preempts the QUEUE, the
    pool reclaims slots only under memory pressure). FIFO scheduling on
    the identical traffic keeps arrival order — the bench's baseline."""
    cfg, params, solo = served["nemotron-4-15b"]
    rng = np.random.RandomState(11)
    reqs = [(rng.randint(0, cfg.vocab_size, size=5).astype(np.int32), 4)
            for _ in range(4)]
    orders = {}
    for mode in ("edf", "fifo"):
        sched = PagedContinuousBatchingServer(
            cfg, params, num_slots=1, max_len=48, block_size=8,
            segment=4, scheduling=mode)
        for rid, (p, g) in enumerate(reqs):
            sched.submit(p, g, priority=(1 if rid == 3 else 0))
        order = []
        while sched._has_work():
            order.extend(r.rid for r in sched.step(draining=True))
        orders[mode] = order
        _check_exact(solo, [r for r in sched.finished], reqs)
    assert orders["fifo"] == [0, 1, 2, 3]
    assert orders["edf"].index(3) < orders["edf"].index(1)
    assert orders["edf"].index(3) < orders["edf"].index(2)


def test_edf_orders_by_deadline_inside_a_class(served):
    cfg, params, _ = served["nemotron-4-15b"]
    sched = PagedContinuousBatchingServer(
        cfg, params, num_slots=1, max_len=48, block_size=8, segment=4)
    t = [0.0]
    sched._clock = lambda: t[0]                     # injectable clock
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size, size=5).astype(np.int32)
               for _ in range(3)]
    sched.submit(prompts[0], 3)                     # no target: best-effort
    sched.submit(prompts[1], 3, ttft_target=100.0)
    sched.submit(prompts[2], 3, ttft_target=1.0)    # tightest deadline
    done = []
    while sched._has_work():
        done.extend(sched.step(draining=True))
    assert [r.rid for r in done] == [2, 1, 0]
    # each finished request carries its own dispatch-time latencies
    assert all(r.ttft >= 0.0 for r in done)
    assert all(r.itl >= 0.0 for r in done)


def test_scheduling_mode_validated(served):
    cfg, params, _ = served["nemotron-4-15b"]
    with pytest.raises(ValueError, match="scheduling"):
        PagedContinuousBatchingServer(
            cfg, params, num_slots=1, max_len=48, block_size=8,
            scheduling="lifo")


# ---------------------------------------------------------------------------
# Satellite: cancel() on both servers.
# ---------------------------------------------------------------------------

def test_cancel_on_slab_server(served):
    cfg, params, solo = served["nemotron-4-15b"]
    sched = ContinuousBatchingServer(cfg, params, num_slots=2,
                                     max_len=48, segment=4)
    rng = np.random.RandomState(9)
    reqs = [(rng.randint(0, cfg.vocab_size, size=5).astype(np.int32), 8)
            for _ in range(4)]
    for p, g in reqs:
        sched.submit(p, g)
    sched.step()                        # rids 0,1 active; 2,3 pending
    assert sched.cancel(2)              # pending
    assert sched.cancel(0)              # active mid-generation
    assert not sched.cancel(2)          # already gone
    assert not sched.cancel(99)         # never existed
    done = sched.run()
    assert sorted(r.rid for r in done) == [1, 3]
    assert sched.stats.cancelled == 2
    _check_exact(solo, done, reqs)      # survivors unperturbed


def test_cancel_on_paged_server_everywhere(served):
    """Cancel a request in every pool-holding state — active, staged,
    spilled — and the pool drains to zero with the survivors exact."""
    cfg, params, solo = served["nemotron-4-15b"]
    sched = _tight_server(cfg, params, num_blocks=6)
    reqs = _tight_traffic(cfg, n=3)
    for p, g in reqs:
        sched.submit(p, g)
    # run until someone spills, then cancel the spilled request
    while not sched._spilled and sched._has_work():
        sched.step(draining=True)
    assert sched._spilled, "expected a spill under this pool"
    spilled_rid = sched._spilled[0].req.rid
    assert sched.cancel(spilled_rid)
    assert spilled_rid not in sched.spill
    # cancel an active one too (if any survive this boundary)
    active = [s.rid for s in sched.slots if not s.free]
    cancelled = {spilled_rid}
    if active:
        assert sched.cancel(active[0])
        cancelled.add(active[0])
    done = sched.run()
    assert {r.rid for r in done} == set(range(3)) - cancelled
    assert sched.stats.cancelled == len(cancelled)
    _check_exact(solo, done, reqs)
    _assert_quiescent(sched)


# ---------------------------------------------------------------------------
# Default traffic is untouched by the machinery (regression guard).
# ---------------------------------------------------------------------------

def test_default_traffic_sees_no_preemption(served):
    """An amply provisioned pool never preempts, never spills, and EDF
    with no priorities or deadlines is exactly FIFO — the overload
    machinery is invisible until overload."""
    cfg, params, solo = served["nemotron-4-15b"]
    sched = PagedContinuousBatchingServer(
        cfg, params, num_slots=2, max_len=48, block_size=8, segment=4)
    rng = np.random.RandomState(17)
    reqs = [(rng.randint(0, cfg.vocab_size,
                         size=rng.randint(2, 12)).astype(np.int32),
             int(rng.randint(1, 9))) for _ in range(5)]
    for p, g in reqs:
        sched.submit(p, g)
    done = sched.run()
    assert len(done) == 5
    st = sched.stats
    assert (st.preemptions, st.restores, st.unstaged, st.cancelled,
            st.spilled_blocks, st.restored_blocks) == (0,) * 6
    assert len(sched.spill) == 0
    _check_exact(solo, done, reqs)
