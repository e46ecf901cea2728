"""Mesh-sharded serving: tensor parallelism, the comms model, the fleet.

Fast tests run on the single-device host mesh — the SAME shard_map step
programs as production, with every collective a size-1 identity, so
(1,1)-mesh serving must be BIT-exact against the solo server. The slow
subprocess test forces 8 virtual CPU devices and proves the real thing:
tp=2 paged decode token-exact on GQA / int8-KV / MLA+MoE, the analytic
per-step collective model equal to the HLO-counted bytes, and the TP
divisibility guard.
"""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfglib
from repro.launch.mesh import (
    CANONICAL_AXES,
    make_host_mesh,
    make_serving_mesh,
    mesh_info,
)
from repro.launch.router import ReplicaRouter, sum_stats
from repro.launch.scheduler import (
    PagedContinuousBatchingServer,
    SchedulerStats,
)
from repro.launch.serve import Server
from repro.models.registry import get_model


def _cfg(arch="nemotron-4-15b"):
    cfg = cfglib.get_smoke_config(arch)
    if cfg.num_experts:
        cfg = dataclasses.replace(cfg,
                                  capacity_factor=float(cfg.num_experts))
    return cfg


@pytest.fixture(scope="module")
def nemotron():
    cfg = _cfg()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _traffic(cfg, n, seed=0):
    rng = np.random.RandomState(seed)
    return [
        (rng.randint(0, cfg.vocab_size, size=rng.randint(3, 12))
         .astype(np.int32), int(rng.randint(2, 7)))
        for _ in range(n)
    ]


# -- mesh construction -------------------------------------------------------

def test_host_mesh_axes_and_sizes():
    for multi_pod in (False, True):
        mesh = make_host_mesh(multi_pod=multi_pod)
        assert tuple(mesh.axis_names) == CANONICAL_AXES[
            3 if multi_pod else 2]
        assert all(s == 1 for s in mesh.devices.shape)
        minfo = mesh_info(mesh)
        assert minfo.size("model") == 1
        assert minfo.tp == "model"


def test_mesh_info_rejects_divergent_axis_names():
    from repro.parallel.compat import auto_mesh

    rogue = auto_mesh((1, 1), ("rows", "cols"))
    with pytest.raises(ValueError, match="canonical"):
        mesh_info(rogue)


def test_serving_mesh_rejects_bad_rank():
    with pytest.raises(ValueError, match="rank"):
        make_serving_mesh((1,))
    with pytest.raises(ValueError, match="rank"):
        make_serving_mesh((1, 1, 1, 1))


# -- host-mesh bit-exactness + cache keys ------------------------------------

def test_host_mesh_paged_serving_bit_exact(nemotron):
    """(1,1)-mesh paged serving (shard_map, size-1 collectives) produces
    EXACTLY the solo server's tokens — the identity end of the TP
    correctness bar; the slow test covers the tp=2 end."""
    cfg, params = nemotron
    solo = Server(cfg, params, max_len=48)
    srv = PagedContinuousBatchingServer(
        cfg, params, num_slots=4, max_len=48, block_size=8,
        mesh=make_host_mesh())
    reqs = _traffic(cfg, 5, seed=11)
    for prompt, gen in reqs:
        srv.submit(prompt, gen)
    done = {r.rid: r for r in srv.run()}
    assert len(done) == len(reqs)
    for rid, (prompt, gen) in enumerate(reqs):
        ref = solo.generate(jnp.asarray(prompt)[None, :], gen,
                            decode="loop")
        np.testing.assert_array_equal(
            np.asarray(ref.tokens)[0, prompt.size:], done[rid].tokens,
            err_msg=f"rid {rid}: host-mesh paged != solo",
        )


def test_executable_cache_keys_carry_mesh(nemotron):
    """Satellite: every paged/stage/segment executable key ends in the
    (mesh shape, axis names) pair — meshless servers record None there,
    so a rebuilt-on-a-mesh server can never replay a stale program."""
    cfg, params = nemotron

    def serve_one(mesh):
        srv = PagedContinuousBatchingServer(
            cfg, params, num_slots=2, max_len=48, block_size=8,
            mesh=mesh)
        srv.submit(np.arange(1, 6, dtype=np.int32), 3)
        srv.run()
        return srv.executable_cache_keys()

    meshless = serve_one(None)
    meshed = serve_one(make_host_mesh())
    assert meshless and meshed
    assert all(k[-1] is None for k in meshless)
    want = ((1, 1), ("data", "model"))
    assert all(k[-1] == want for k in meshed)
    # identical traffic, disjoint key spaces
    assert not set(meshless) & set(meshed)


def test_replicated_tables_stay_valid_under_eviction(nemotron):
    """The host-side block tables are THE replicated metadata of the TP
    design (every shard receives the same (N, nb) int table). Serve
    enough shared-prefix traffic through a deliberately tiny pool to
    force evictions, and check the invariants the device path promises
    on: in-bounds tables at every dispatch (validate_tables raises
    inside run() otherwise), exact tokens, and allocator bookkeeping
    that sums back to capacity."""
    cfg, params = nemotron
    solo = Server(cfg, params, max_len=48)
    srv = PagedContinuousBatchingServer(
        cfg, params, num_slots=2, max_len=48, block_size=8,
        num_blocks=9, mesh=make_host_mesh())
    rng = np.random.RandomState(5)
    reqs = []
    for i in range(8):
        # unique >=1-full-block prompts: each publishes a prefix block
        # that turns cached on release, so the tiny free list runs dry
        # and later admissions must evict
        prompt = rng.randint(0, cfg.vocab_size,
                             size=int(rng.randint(9, 13))).astype(np.int32)
        reqs.append((prompt, 8))
        srv.submit(prompt, 8)
    done = {r.rid: r for r in srv.run()}
    assert len(done) == len(reqs)
    for rid, (prompt, gen) in enumerate(reqs):
        ref = solo.generate(jnp.asarray(prompt)[None, :], gen,
                            decode="loop")
        np.testing.assert_array_equal(
            np.asarray(ref.tokens)[0, prompt.size:], done[rid].tokens)
    assert srv.stats.evictions > 0, "pool never came under pressure"
    alloc = srv.mgr.alloc
    assert (alloc.num_free + alloc.num_evictable + alloc.in_use
            == alloc.capacity)
    # drained fleet: no slot still points at real blocks
    assert (srv._tables == 0).all()


# -- replica router ----------------------------------------------------------

def _fleet(cfg, params, n, policy, **kw):
    reps = [
        PagedContinuousBatchingServer(cfg, params, num_slots=2,
                                      max_len=64, block_size=8)
        for _ in range(n)
    ]
    return ReplicaRouter(reps, policy=policy, **kw)


def _prefix_waves(cfg, n_fams=4, waves=3, per_wave=8, seed=7):
    rng = np.random.RandomState(seed)
    fams = [rng.randint(0, cfg.vocab_size, size=24).astype(np.int32)
            for _ in range(n_fams)]
    out = []
    for _ in range(waves):
        wave = []
        for i in range(per_wave):
            tail = rng.randint(0, cfg.vocab_size,
                               size=rng.randint(2, 6)).astype(np.int32)
            wave.append((np.concatenate([fams[i % n_fams], tail]),
                         int(rng.randint(2, 5))))
        out.append(wave)
    return out


def test_router_prefix_affinity_beats_random(nemotron):
    """Shared-prefix waves over 4 replicas: after the seeding wave the
    prefix policy concentrates each family on the replica holding its
    blocks, so the fleet prefix hit rate must beat random spray (and
    affinity routing must actually fire — not win vacuously)."""
    cfg, params = nemotron
    waves = _prefix_waves(cfg)
    rates = {}
    for policy in ("prefix", "random"):
        fleet = _fleet(cfg, params, 4, policy, seed=3)
        fids = []
        for wave in waves:
            fids += [fleet.submit(p, g) for p, g in wave]
            fleet.run()
        assert fleet.load == 0
        rates[policy] = fleet.stats.prefix_hit_rate
        if policy == "prefix":
            assert fleet.stats.affinity_routed > 0
            assert fleet.stats.random_routed == 0
        else:
            assert fleet.stats.random_routed == len(fids)
        # fleet rids are unique and dense
        assert sorted(fids) == list(range(len(fids)))
    assert rates["prefix"] > rates["random"], rates


def test_router_tokens_match_solo_and_stats_roll_up(nemotron):
    cfg, params = nemotron
    solo = Server(cfg, params, max_len=64)
    fleet = _fleet(cfg, params, 2, "prefix")
    reqs = _traffic(cfg, 6, seed=2)
    fids = [fleet.submit(p, g) for p, g in reqs]
    done = {r.rid: r for r in fleet.run()}
    assert sorted(done) == sorted(fids)
    for fid, (prompt, gen) in zip(fids, reqs):
        ref = solo.generate(jnp.asarray(prompt)[None, :], gen,
                            decode="loop")
        np.testing.assert_array_equal(
            np.asarray(ref.tokens)[0, prompt.size:], done[fid].tokens,
            err_msg=f"fleet fid {fid} != solo",
        )
    totals = fleet.stats.totals
    assert totals.admitted == len(reqs)
    assert totals.segments == sum(r.stats.segments
                                  for r in fleet.replicas)
    assert fleet.stats.requests == len(reqs)


def test_sum_stats_adds_every_counter_field():
    fields = dataclasses.fields(SchedulerStats)
    a = SchedulerStats(**{f.name: i + 1 for i, f in enumerate(fields)})
    b = SchedulerStats(**{f.name: 10 * (i + 1)
                          for i, f in enumerate(fields)})
    s = sum_stats([a, b])
    for f in fields:
        assert getattr(s, f.name) == getattr(a, f.name) + getattr(b, f.name)


def test_router_rejects_bad_policy_and_empty_fleet(nemotron):
    cfg, params = nemotron
    with pytest.raises(ValueError, match="policy"):
        _fleet(cfg, params, 1, "round-robin")
    with pytest.raises(ValueError, match="replica"):
        ReplicaRouter([])


def test_prefix_affinity_probe_is_side_effect_free(nemotron):
    cfg, params = nemotron
    srv = PagedContinuousBatchingServer(
        cfg, params, num_slots=2, max_len=64, block_size=8)
    prompt = np.arange(1, 20, dtype=np.int32)
    srv.submit(prompt, 4)
    srv.run()
    before = dataclasses.replace(srv.mgr.counters)
    hits = srv.mgr.prefix_affinity(prompt)
    assert hits == (prompt.size - 1) // 8
    assert srv.mgr.counters == before, "peek must not move counters"


# -- the comms model (identity end) ------------------------------------------

def test_tp_step_collectives_zero_at_tp1():
    from repro.launch.roofline import tp_step_collectives

    model = tp_step_collectives(_cfg(), batch=4, tp=1)
    assert all(v == 0.0 for v in model.values())


def test_tp_spec_host_mesh_places_everything(nemotron):
    cfg, params = nemotron
    srv = Server(cfg, params, max_len=32, mesh=make_host_mesh())
    assert srv.tp is not None
    assert srv.tp.size == 1
    assert srv.tp.mesh_key == ((1, 1), ("data", "model"))
    assert srv.tp.cfg_local.num_heads == cfg.num_heads


# -- the real thing: 8 virtual devices, tp=2 ---------------------------------

@pytest.mark.slow
def test_tp2_serving_subprocess():
    """8 forced CPU devices, (4,2) mesh: paged-kernel serving at tp=2 is
    token-exact vs solo for GQA, int8-KV and MLA+MoE (Pallas interpret
    kernels on for the non-quantized families); the analytic collective
    model equals the loop-aware HLO count for step and scanned segment;
    indivisible head counts are rejected."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import configs as cfglib
from repro.launch import hlo_analysis, roofline
from repro.launch.mesh import make_serving_mesh
from repro.launch.scheduler import PagedContinuousBatchingServer
from repro.launch.serve import Server, make_decode_scan, make_tp_spec
from repro.models.registry import get_model

mesh = make_serving_mesh((4, 2))

def smoke(arch):
    if arch == "nemotron-int8":
        cfg = dataclasses.replace(
            cfglib.get_smoke_config("nemotron-4-15b"),
            kv_cache_dtype=jnp.int8)
    else:
        cfg = cfglib.get_smoke_config(arch)
    if cfg.num_experts:
        cfg = dataclasses.replace(cfg,
                                  capacity_factor=float(cfg.num_experts))
    return cfg

for arch in ("nemotron-4-15b", "nemotron-int8", "deepseek-v3-671b"):
    cfg = smoke(arch)
    if arch != "nemotron-int8":     # int8 KV takes the ref path anyway
        cfg = dataclasses.replace(cfg, use_pallas=True)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    solo = Server(cfg, params, max_len=48)
    srv = PagedContinuousBatchingServer(
        cfg, params, num_slots=4, max_len=48, block_size=8, mesh=mesh)
    assert srv.tp is not None and srv.tp.size == 2
    rng = np.random.RandomState(3)
    reqs = [(rng.randint(0, cfg.vocab_size, size=rng.randint(3, 12))
             .astype(np.int32), int(rng.randint(2, 7)))
            for _ in range(5)]
    for p, g in reqs:
        srv.submit(p, g)
    done = {r.rid: r for r in srv.run()}
    for rid, (p, g) in enumerate(reqs):
        ref = solo.generate(jnp.asarray(p)[None, :], g, decode="loop")
        got = np.asarray(done[rid].tokens)
        want = np.asarray(ref.tokens)[0, p.size:]
        assert got.tolist() == want.tolist(), (arch, rid, got, want)
    print(arch, "tp2 token-exact")

# comms model == HLO (single step and 6-step scanned segment)
cfg = smoke("nemotron-4-15b")
api = get_model(cfg)
srv = Server(cfg, api.init(jax.random.PRNGKey(1), cfg), max_len=32,
             mesh=mesh)
B = 4
cache = srv.tp.place_cache(api.init_cache(cfg, srv.minfo, B, 32))
toks = jnp.zeros((B, 1), jnp.int32)
for steps in (1, 6):
    scan = make_decode_scan(cfg, api, srv.minfo, mesh, steps, tp=srv.tp)
    comp = jax.jit(scan).lower(
        srv.params, toks, cache, jnp.int32(3), None, None).compile()
    costs = hlo_analysis.analyze_hlo(comp.as_text())
    model = roofline.tp_step_collectives(cfg, batch=B, tp=2, steps=steps)
    assert costs.unknown_trip_loops == 0
    for kind, want in model.items():
        got = costs.coll_bytes.get(kind, 0.0)
        assert got == want, (steps, kind, got, want)
    print("comms model == HLO for", steps, "step(s)")

# divisibility guard
bad = dataclasses.replace(cfg, num_heads=3, num_kv_heads=3, head_dim=8)
try:
    make_tp_spec(bad, get_model(bad), mesh)
except ValueError as e:
    assert "divide" in str(e) or "model" in str(e), e
    print("divisibility guard OK")
else:
    raise AssertionError("indivisible heads were accepted")
print("SUBPROCESS OK")
"""
    res = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    assert res.returncode == 0, res.stdout + res.stderr
    assert "SUBPROCESS OK" in res.stdout
