"""Measured serving-path benchmark: scan-compiled decode + batching.

Three comparisons, all wall-clock on this host (CPU numbers are not TPU
numbers, but the *mechanisms* measured — dispatch count, retrace count,
slot utilization — are backend-independent):

  loop_vs_scan   — the PR-2 one-jitted-dispatch-per-token Python decode
                   loop vs the same decode compiled into ONE program
                   (``jax.lax.scan`` with the cache donated through the
                   carry). Reports tokens/s; for the loop, real
                   per-dispatch p50/p95 (each decode dispatch timed);
                   for the scan, the amortized per-token cost
                   (wall/steps) — the loop pays a host->device dispatch
                   per token, the scan pays one per generation.
  flat_vs_plan   — a uniform plan served by the scanned layer stack vs a
                   heterogeneous per-layer ``ExecutionPlan`` (unrolled
                   stack, one kernel-variant trace per layer). Measures
                   the serving-layer cost of per-layer dispatch; the
                   kernel-level payoff of the per-layer depth choice is
                   the depth_sweep section of fusion_bench.
  continuous     — mixed-length traffic through the slot scheduler (ONE
                   batched segment program over all occupied slots at
                   per-row positions; batched admission fused into a
                   single gather-prefill-correct-scatter dispatch) vs
                   static batching that pads every request to the batch
                   max. Useful-token throughput over a traffic-mix sweep
                   (homogeneous -> uniform -> heavy-tailed generation
                   lengths) on a compute-dominated smoke config; the
                   measured crossover records the first mix where
                   continuous wins (static batching's padding waste
                   outgrows the scheduler's boundary overhead).

Rows are ``(tag, us_per_token, derived)`` where derived is tokens/s
(or a ratio for the summary rows), so ``benchmarks/run.py serving
--json BENCH_serving.json`` emits the machine-readable trajectory file.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfglib
from repro.core.modes import ExecutionMode, ExecutionPlan, LayerPlan
from repro.launch.scheduler import (
    ContinuousBatchingServer,
    PagedContinuousBatchingServer,
)
from repro.launch.serve import Server
from repro.models.registry import get_model

ARCH = "nemotron-4-15b"
BATCH, PROMPT, GEN = 4, 16, 32
TRIALS = 5


def _setup(arch: str = ARCH, **server_kw):
    cfg = cfglib.get_smoke_config(arch)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    server = Server(cfg, params, max_len=PROMPT + GEN + 8, **server_kw)
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (BATCH, PROMPT), 0, cfg.vocab_size,
        dtype=jnp.int32,
    )
    return cfg, params, server, prompts


def _pct(samples, q):
    return float(np.percentile(np.asarray(samples), q))


def _loop_token_latencies(server, prompts, gen):
    """Per-DISPATCH decode latencies for the loop path: drive the same
    jitted single-step the loop uses and time each dispatch (a whole-
    generate wall divided by N would hide the per-token tail)."""
    from repro.kernels import ops as kops

    b, s = prompts.shape
    samples = []
    with kops.execution_plan(server.plan):
        cache = server._take_cache(b)
        nxt, cache = server._prefill(server.params,
                                     {"tokens": prompts}, cache)
        pos = s
        for _ in range(gen - 1):
            t0 = time.perf_counter()
            nxt, cache = server._decode(server.params, nxt, cache,
                                        jnp.int32(pos), None)
            jax.block_until_ready(nxt)
            samples.append((time.perf_counter() - t0) * 1e6)
            pos += 1
    server._return_cache(b, cache)
    return samples


def loop_vs_scan_rows():
    _, _, server, prompts = _setup()
    out = []
    tag = f"serving/{ARCH}/b{BATCH}_g{GEN}"

    # loop: throughput over whole generates + real per-dispatch p50/p95
    server.generate(prompts, GEN, decode="loop")  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(TRIALS):
        jax.block_until_ready(
            server.generate(prompts, GEN, decode="loop").tokens)
    loop_wall = time.perf_counter() - t0
    loop_tok_s = TRIALS * GEN * BATCH / loop_wall
    loop_us = _loop_token_latencies(server, prompts, GEN)

    # scan: one dispatch per generation — per-token latency only exists
    # amortized (that is the point), reported as wall/steps per trial
    server.generate(prompts, GEN, decode="scan")  # warmup/compile
    scan_amort_us = []
    t0 = time.perf_counter()
    for _ in range(TRIALS):
        t1 = time.perf_counter()
        jax.block_until_ready(
            server.generate(prompts, GEN, decode="scan").tokens)
        scan_amort_us.append((time.perf_counter() - t1) * 1e6 / GEN)
    scan_wall = time.perf_counter() - t0
    scan_tok_s = TRIALS * GEN * BATCH / scan_wall

    out.append((f"{tag}/loop/tok_s", float(np.median(loop_us)), loop_tok_s))
    out.append((f"{tag}/loop/p50_us", _pct(loop_us, 50), _pct(loop_us, 50)))
    out.append((f"{tag}/loop/p95_us", _pct(loop_us, 95), _pct(loop_us, 95)))
    out.append((f"{tag}/scan/tok_s", float(np.median(scan_amort_us)),
                scan_tok_s))
    out.append((f"{tag}/scan/amortized_tok_us_p50", _pct(scan_amort_us, 50),
                _pct(scan_amort_us, 50)))
    out.append((f"{tag}/scan/amortized_tok_us_p95", _pct(scan_amort_us, 95),
                _pct(scan_amort_us, 95)))
    out.append((f"{tag}/scan_over_loop_speedup", 0.0,
                scan_tok_s / loop_tok_s))
    return out


def flat_vs_plan_rows():
    depth2 = LayerPlan(ExecutionMode.SIDEBAR_PIPELINED, depth=2)
    depth4 = LayerPlan(ExecutionMode.SIDEBAR_PIPELINED, depth=4)
    cfg = cfglib.get_smoke_config(ARCH)
    per_layer = ExecutionPlan(
        default=depth2,
        layers={i: (depth4 if i % 2 else depth2)
                for i in range(cfg.num_layers)},
    )
    out = []
    for name, plan in (("flat", depth2), ("per_layer", per_layer)):
        _, _, server, prompts = _setup(plan=plan)
        server.generate(prompts, GEN)  # warmup/compile
        t0 = time.perf_counter()
        for _ in range(TRIALS):
            jax.block_until_ready(server.generate(prompts, GEN).tokens)
        wall = time.perf_counter() - t0
        tok_s = TRIALS * GEN * BATCH / wall
        out.append((f"serving/{ARCH}/plan_{name}/tok_s",
                    wall * 1e6 / (TRIALS * GEN), tok_s))
    out.append((f"serving/{ARCH}/per_layer_over_flat", 0.0,
                out[1][2] / out[0][2]))
    return out


# continuous-vs-static runs on a compute-dominated smoke config (d=256,
# 4 layers — still seconds on CPU): at the tiny test size a single XLA
# dispatch costs as much as several decode steps, so the comparison
# measures Python/dispatch overhead instead of scheduler mechanics. The
# traffic sweep moves from homogeneous generation lengths (static
# batching's best case: zero padding waste) to a heavy-tailed chat-like
# mix (many short answers, a few long ones — every static batch pads to
# its longest member); the measured crossover is the first mix where
# batched segment decode wins.
CONT_SLOTS, CONT_REQS, CONT_TRIALS = 4, 24, 3
TRAFFIC_MIXES = ("uniform_28_32", "uniform_8_32", "heavy_tail")


def _continuous_cfg():
    import dataclasses

    return dataclasses.replace(
        cfglib.get_smoke_config(ARCH), d_model=256, num_heads=8,
        num_kv_heads=2, d_ff=1024, num_layers=4,
    )


def _traffic(cfg, mix: str):
    rng = np.random.RandomState(7)
    if mix == "uniform_28_32":
        gens = [int(rng.randint(28, GEN)) for _ in range(CONT_REQS)]
    elif mix == "uniform_8_32":
        gens = [int(rng.randint(8, GEN)) for _ in range(CONT_REQS)]
    else:  # heavy_tail: 3/4 short chat answers, 1/4 long generations
        n_long = CONT_REQS // 4
        gens = [int(rng.randint(2, 7)) for _ in range(CONT_REQS - n_long)]
        gens += [int(rng.randint(28, GEN)) for _ in range(n_long)]
        rng.shuffle(gens)
    return [
        (rng.randint(0, cfg.vocab_size, size=rng.randint(4, 15)).astype(
            np.int32), g)
        for g in gens
    ]


def _measure_mix(cfg, params, server, reqs):
    """Interleaved paired trials (continuous then static per trial) so
    host noise cancels in the ratio; returns medians."""
    useful = sum(g for _, g in reqs)
    max_len = PROMPT + GEN + 8
    sched = ContinuousBatchingServer(
        cfg, params, num_slots=CONT_SLOTS, max_len=max_len, buckets=(16,),
        segment=8,
    )
    for p, g in reqs:
        sched.submit(p, g)
    sched.run()  # warmup: compiles every (bucket, steps, plan) executable
    batches = [reqs[i:i + CONT_SLOTS]
               for i in range(0, CONT_REQS, CONT_SLOTS)]

    def run_static():
        # static baseline: fixed batches of `slots`, padded to the batch
        # max prompt/gen (Server pads nothing itself: bucket by hand).
        for batch in batches:
            s_max = max(p.size for p, _ in batch)
            g_max = max(g for _, g in batch)
            toks = np.zeros((len(batch), s_max), np.int32)
            for j, (p, _) in enumerate(batch):
                toks[j, :p.size] = p  # right-pad (throughput-only proxy)
            jax.block_until_ready(
                server.generate(jnp.asarray(toks), g_max).tokens)

    run_static()  # warmup
    ratios, cont, static = [], [], []
    for _ in range(CONT_TRIALS):
        for p, g in reqs:
            sched.submit(p, g)
        t0 = time.perf_counter()
        sched.run()
        cw = time.perf_counter() - t0
        t0 = time.perf_counter()
        run_static()
        sw = time.perf_counter() - t0
        ratios.append(sw / cw)
        cont.append(useful / cw)
        static.append(useful / sw)
    # report the median-RATIO trial's own numbers so the three rows stay
    # self-consistent (independent medians can disagree with the paired
    # ratio under host noise)
    mid = int(np.argsort(ratios)[len(ratios) // 2])
    return cont[mid], static[mid], ratios[mid], sched


def continuous_rows():
    cfg = _continuous_cfg()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    server = Server(cfg, params, max_len=PROMPT + GEN + 8)

    out = []
    ratios = {}
    sched = None
    for mix in TRAFFIC_MIXES:
        reqs = _traffic(cfg, mix)
        cont, static, ratio, sched = _measure_mix(cfg, params, server, reqs)
        ratios[mix] = ratio
        if mix == "heavy_tail":  # the flagship comparison
            out.append((f"serving/{ARCH}/continuous/tok_s", 1e6 / cont,
                        cont))
            out.append((f"serving/{ARCH}/static_batch/tok_s", 1e6 / static,
                        static))
            out.append((f"serving/{ARCH}/continuous_over_static", 0.0,
                        ratio))
        out.append((f"serving/{ARCH}/continuous_over_static/{mix}", 0.0,
                    ratio))
    # measured crossover: 1-based index (in increasing traffic
    # heterogeneity) of the first mix where continuous wins; 0 = never
    crossover = next((i + 1 for i, m in enumerate(TRAFFIC_MIXES)
                      if ratios[m] >= 1.0), 0)
    out.append((f"serving/{ARCH}/continuous_crossover_mix", 0.0,
                float(crossover)))
    # idle-row fraction: free/dead slot rows the batched segment
    # programs decode alongside active ones (shrink-to-fit already makes
    # active-slot overshoot zero), per active decode step
    out.append((f"serving/{ARCH}/continuous/wasted_step_frac", 0.0,
                sched.stats["wasted_steps"] /
                max(sched.stats["decode_steps"], 1)))
    return out


# paged-vs-synchronous-admission runs the heavy-tail mix with a shared
# system prefix (the realistic chat shape: every request front-loads the
# same instructions). The slab scheduler prefills every prompt at its
# admission boundary (synchronous admission, PR-4); the paged scheduler
# splices the shared prefix out of the block index and stages the rest
# chunk-by-chunk between segments (prefill-ahead), so the prefill compute
# the slab path repeats per request mostly disappears. Interleaved paired
# trials as in _measure_mix.
PAGED_BLOCK, PAGED_PREFIX, PAGED_TRIALS = 8, 24, 5


def _prefix_traffic(cfg):
    rng = np.random.RandomState(7)
    system = rng.randint(0, cfg.vocab_size, size=PAGED_PREFIX).astype(
        np.int32)
    n_long = CONT_REQS // 4
    gens = [int(rng.randint(2, 7)) for _ in range(CONT_REQS - n_long)]
    gens += [int(rng.randint(28, GEN)) for _ in range(n_long)]
    rng.shuffle(gens)
    return [
        (np.concatenate([system, rng.randint(
            0, cfg.vocab_size, size=rng.randint(2, 7)).astype(np.int32)]),
         g)
        for g in gens
    ]


def paged_rows():
    cfg = _continuous_cfg()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    reqs = _prefix_traffic(cfg)
    useful = sum(g for _, g in reqs)
    max_len = PAGED_PREFIX + 8 + GEN  # prompt <= prefix+7, gen <= GEN
    paged = PagedContinuousBatchingServer(
        cfg, params, num_slots=CONT_SLOTS, max_len=max_len,
        block_size=PAGED_BLOCK, prefill_chunk=PAGED_BLOCK, segment=8,
    )
    slab = ContinuousBatchingServer(
        cfg, params, num_slots=CONT_SLOTS, max_len=max_len,
        buckets=(16, 32), segment=8,
    )

    def run(server):
        for p, g in reqs:
            server.submit(p, g)
        t0 = time.perf_counter()
        server.run()
        return time.perf_counter() - t0

    for _ in range(2):     # warmup: compile + populate the prefix index
        run(paged), run(slab)
    hits0 = paged.stats.prefix_block_hits        # measured trials only
    lookups0 = paged.stats.prefix_block_lookups
    ratios, pg, sy = [], [], []
    for _ in range(PAGED_TRIALS):
        pw = run(paged)
        sw = run(slab)
        ratios.append(sw / pw)
        pg.append(useful / pw)
        sy.append(useful / sw)
    mid = int(np.argsort(ratios)[len(ratios) // 2])
    hit_rate = (paged.stats.prefix_block_hits - hits0) / max(
        paged.stats.prefix_block_lookups - lookups0, 1)
    return [
        (f"serving/{ARCH}/paged/tok_s", 1e6 / pg[mid], pg[mid]),
        (f"serving/{ARCH}/sync_admission/tok_s", 1e6 / sy[mid], sy[mid]),
        (f"serving/{ARCH}/paged_over_sync_admission", 0.0, ratios[mid]),
        (f"serving/{ARCH}/paged/prefix_hit_rate", 0.0, hit_rate),
        (f"serving/{ARCH}/paged/pool_occupancy_peak", 0.0,
         paged.stats.pool_in_use_peak / max(paged.stats.pool_blocks, 1)),
        (f"serving/{ARCH}/paged/stage_chunks", 0.0,
         float(paged.stats.stage_chunks)),
    ]


def paged_kernel_rows():
    """In-place paged decode (PR-6) vs the slab round-trip it replaced:
    the SAME paged scheduler and shared-prefix heavy-tail traffic, only
    the segment program differs — ``kernel="paged"`` walks the block
    tables in place (attention width sliced to the active frontier),
    ``kernel="slab"`` brackets every segment with pool-wide
    gather_blocks/scatter_blocks and attends full max_len. A generous
    max_len makes the structural difference visible on CPU: the slab
    segment pays for ALL of it every segment, the paged kernel only for
    blocks that can hold live KV. The config trims d_ff so the
    attention/copy work the two kernels disagree on isn't drowned by
    MLP compute identical on both sides. Interleaved paired trials as
    above."""
    import dataclasses

    cfg = dataclasses.replace(_continuous_cfg(), d_ff=256)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    # boundary-heavy traffic: tiny prompts, moderate generations, a
    # deep backlog — pending admissions keep segments short, which is
    # where the kernels structurally differ (the slab pays its pool
    # round-trip at every boundary; the paged kernel pays nothing)
    rng = np.random.RandomState(7)
    reqs = [
        (rng.randint(0, cfg.vocab_size,
                     size=rng.randint(4, 9)).astype(np.int32),
         int(rng.randint(8, 17)))
        for _ in range(32)
    ]
    useful = sum(g for _, g in reqs)
    max_len = 256  # >> live prefixes (<= 63): the slab's fixed cost

    def make(kernel):
        # a right-sized pool (live KV is <= 3 blocks/slot), not the
        # defensive slots*max_len default: pool memory proportional to
        # LIVE data is the paged design's premise, and per-step pool
        # writes cost what the pool occupies
        return PagedContinuousBatchingServer(
            cfg, params, num_slots=CONT_SLOTS, max_len=max_len,
            num_blocks=64, block_size=PAGED_BLOCK,
            prefill_chunk=PAGED_BLOCK, segment=2, kernel=kernel,
        )

    inplace, roundtrip = make("paged"), make("slab")

    def run(server):
        for p, g in reqs:
            server.submit(p, g)
        t0 = time.perf_counter()
        server.run()
        return time.perf_counter() - t0

    for _ in range(2):     # warmup: compile both segment families
        run(inplace), run(roundtrip)
    ratios, pk, sk = [], [], []
    for _ in range(2 * PAGED_TRIALS - 1):  # thin margin: tighter median
        pw = run(inplace)
        sw = run(roundtrip)
        ratios.append(sw / pw)
        pk.append(useful / pw)
        sk.append(useful / sw)
    mid = int(np.argsort(ratios)[len(ratios) // 2])
    return [
        (f"serving/{ARCH}/paged_kernel/tok_s", 1e6 / pk[mid], pk[mid]),
        (f"serving/{ARCH}/paged_slab/tok_s", 1e6 / sk[mid], sk[mid]),
        (f"serving/{ARCH}/paged_kernel_over_slab", 0.0, ratios[mid]),
    ]


# tensor-parallel serving: the SAME paged scheduler, solo vs a (1,2)
# mesh (shard_map step programs, weights/KV split on "model", logits
# all-gathered per step). Runs in a SUBPROCESS so the bench can force
# two virtual CPU devices without disturbing this process's jax. On one
# physical CPU the two "devices" share cores, so the tp row measures
# the sharding + collective OVERHEAD (tp2_over_solo < 1 is expected
# here); the gated invariant is tp_tokens_match — TP must be a pure
# parallelization, token-identical to solo serving.
MESH_TRIALS = 3

_MESH_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import dataclasses, json, time
import jax, jax.numpy as jnp, numpy as np
from repro import configs as cfglib
from repro.launch.mesh import make_serving_mesh
from repro.launch.scheduler import PagedContinuousBatchingServer
from repro.models.registry import get_model

ARCH, PREFIX, GEN, TRIALS = %r, %d, %d, %d
cfg = dataclasses.replace(
    cfglib.get_smoke_config(ARCH), d_model=256, num_heads=8,
    num_kv_heads=2, d_ff=1024, num_layers=4,
)
api = get_model(cfg)
params = api.init(jax.random.PRNGKey(0), cfg)
rng = np.random.RandomState(7)
system = rng.randint(0, cfg.vocab_size, size=PREFIX).astype(np.int32)
reqs = [
    (np.concatenate([system, rng.randint(
        0, cfg.vocab_size, size=rng.randint(2, 7)).astype(np.int32)]),
     int(rng.randint(4, 17)))
    for _ in range(16)
]
useful = sum(g for _, g in reqs)
max_len = PREFIX + 8 + GEN

def make(mesh):
    return PagedContinuousBatchingServer(
        cfg, params, num_slots=4, max_len=max_len, block_size=8,
        prefill_chunk=8, segment=8, mesh=mesh)

solo, tp = make(None), make(make_serving_mesh((1, 2)))

def run(server):
    for p, g in reqs:
        server.submit(p, g)
    t0 = time.perf_counter()
    done = server.run()
    return time.perf_counter() - t0, done

_, d_solo = run(solo)          # warmup: compile + seed prefix index
_, d_tp = run(tp)
match = all(
    np.array_equal(a.tokens, b.tokens)
    for a, b in zip(sorted(d_solo, key=lambda r: r.rid),
                    sorted(d_tp, key=lambda r: r.rid))
) and len(d_solo) == len(d_tp) == len(reqs)
ratios, so, tr = [], [], []
for _ in range(TRIALS):
    sw, ds = run(solo)
    tw, dt = run(tp)
    match = match and all(
        np.array_equal(a.tokens, b.tokens)
        for a, b in zip(sorted(ds, key=lambda r: r.rid),
                        sorted(dt, key=lambda r: r.rid)))
    ratios.append(sw / tw)
    so.append(useful / sw)
    tr.append(useful / tw)
mid = int(np.argsort(ratios)[len(ratios) // 2])
print(json.dumps({"solo_tok_s": so[mid], "tp_tok_s": tr[mid],
                  "ratio": ratios[mid], "match": int(match)}))
"""


def mesh_rows():
    import json
    import os
    import subprocess
    import sys

    if jax.default_backend() == "tpu":
        # this process holds the chip: the child would be forced onto
        # the CPU and its number would pass for a chip measurement
        raise RuntimeError(
            "the mesh rows run in a CPU child process; on a TPU host run "
            "the serving bench with JAX_PLATFORMS=cpu")
    child = _MESH_CHILD % (ARCH, PAGED_PREFIX, GEN, MESH_TRIALS)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    res = subprocess.run(
        [sys.executable, "-c", child], capture_output=True, text=True,
        timeout=900, env=env,
    )
    if res.returncode != 0:
        raise RuntimeError(
            f"mesh bench child failed:\n{res.stdout}\n{res.stderr}")
    data = json.loads(res.stdout.strip().splitlines()[-1])
    return [
        (f"serving/{ARCH}/tp2/tok_s", 1e6 / data["tp_tok_s"],
         data["tp_tok_s"]),
        (f"serving/{ARCH}/tp_solo/tok_s", 1e6 / data["solo_tok_s"],
         data["solo_tok_s"]),
        (f"serving/{ARCH}/tp2_over_solo", 0.0, data["ratio"]),
        (f"serving/{ARCH}/tp_tokens_match", 0.0, float(data["match"])),
    ]


# replica-router fleet: 4 paged replicas, shared-prefix wave traffic,
# prefix-affinity steering vs random spray. Greedy decode + seeded
# traffic + seeded router make BOTH hit rates deterministic, so the
# affinity-over-random ratio is a hard-gateable invariant, not a timing.
FLEET_REPLICAS, FLEET_WAVES, FLEET_PER_WAVE, FLEET_FAMILIES = 4, 3, 8, 4


def _fleet_waves(cfg):
    rng = np.random.RandomState(7)
    fams = [rng.randint(0, cfg.vocab_size, size=PAGED_PREFIX).astype(
        np.int32) for _ in range(FLEET_FAMILIES)]
    waves = []
    for _ in range(FLEET_WAVES):
        wave = []
        for i in range(FLEET_PER_WAVE):
            tail = rng.randint(0, cfg.vocab_size,
                               size=rng.randint(2, 7)).astype(np.int32)
            wave.append((np.concatenate([fams[i % FLEET_FAMILIES], tail]),
                         int(rng.randint(2, 7))))
        waves.append(wave)
    return waves


def router_rows():
    from repro.launch.router import ReplicaRouter

    cfg = _continuous_cfg()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    waves = _fleet_waves(cfg)
    max_len = PAGED_PREFIX + 8 + GEN
    rates = {}
    for policy in ("prefix", "random"):
        replicas = [
            PagedContinuousBatchingServer(
                cfg, params, num_slots=2, max_len=max_len,
                block_size=PAGED_BLOCK, prefill_chunk=PAGED_BLOCK,
                segment=8)
            for _ in range(FLEET_REPLICAS)
        ]
        fleet = ReplicaRouter(replicas, policy=policy, seed=3)
        for wave in waves:
            for p, g in wave:
                fleet.submit(p, g)
            fleet.run()   # drain between waves so the index seeds
        rates[policy] = fleet.stats.prefix_hit_rate
    return [
        (f"serving/{ARCH}/fleet_prefix_hit_rate", 0.0, rates["prefix"]),
        (f"serving/{ARCH}/fleet_random_hit_rate", 0.0, rates["random"]),
        (f"serving/{ARCH}/router_affinity_over_random", 0.0,
         rates["prefix"] / max(rates["random"], 1e-9)),
    ]


# speculative decode: the SAME paged scheduler and heavy-tail traffic,
# spec (oracle draft, k tokens verified per step) vs plain segment
# decode. An oracle draft (draft == target) accepts everything, so the
# spec arm takes exactly ceil(gen/(k+1)) verify dispatches where plain
# takes gen segment steps — the measured ratio is the dispatch-count
# mechanism, but on this host the small draft is NOT small (it IS the
# target), so spec_over_plain is recorded, never gated. The gated
# invariant is spec_tokens_match: speculation must be invisible in the
# emitted stream, bit for bit.
SPEC_K = 3


def spec_rows():
    from repro.launch.spec import SpecConfig

    cfg = _continuous_cfg()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    reqs = _traffic(cfg, "heavy_tail")
    useful = sum(g for _, g in reqs)
    max_len = PROMPT + GEN + 8

    def make(spec):
        return PagedContinuousBatchingServer(
            cfg, params, num_slots=CONT_SLOTS, max_len=max_len,
            block_size=PAGED_BLOCK, prefill_chunk=PAGED_BLOCK, segment=8,
            spec=spec)

    spec = make(SpecConfig(draft_cfg=cfg, draft_params=params, k=SPEC_K))
    plain = make(None)

    def run(server):
        for p, g in reqs:
            server.submit(p, g)
        t0 = time.perf_counter()
        done = server.run()
        return time.perf_counter() - t0, done

    _, d_spec = run(spec)       # warmup: compile draft/verify/stage
    _, d_plain = run(plain)

    def tokens(done):
        return {r.rid: np.asarray(r.tokens) for r in done}

    match = (len(d_spec) == len(d_plain) == len(reqs)
             and all(np.array_equal(t, tokens(d_plain)[rid])
                     for rid, t in tokens(d_spec).items()))
    ratios, sp, pl = [], [], []
    for _ in range(PAGED_TRIALS):
        sw, ds = run(spec)
        pw, dp = run(plain)
        match = match and all(np.array_equal(t, tokens(dp)[rid])
                              for rid, t in tokens(ds).items())
        ratios.append(pw / sw)
        sp.append(useful / sw)
        pl.append(useful / pw)
    mid = int(np.argsort(ratios)[len(ratios) // 2])
    return [
        (f"serving/{ARCH}/spec/tok_s", 1e6 / sp[mid], sp[mid]),
        (f"serving/{ARCH}/spec_plain/tok_s", 1e6 / pl[mid], pl[mid]),
        (f"serving/{ARCH}/spec_over_plain", 0.0, ratios[mid]),
        (f"serving/{ARCH}/spec_tokens_match", 0.0, float(match)),
        (f"serving/{ARCH}/spec/acceptance_rate", 0.0,
         spec.stats.spec_acceptance_rate),
    ]


# overload: the fleet at 2x oversubscription. A low-priority backlog
# saturates every slot on a pool sized so two fully grown spans fill it
# (lazy allocation's pressure case), then high-priority requests land
# MID-DRAIN with a TTFT target. Under EDF the arrival stages by
# reclaiming from strictly worse holders — spilling an active low to
# the sidebar region — and admits within a boundary or two; under FIFO
# it waits out the whole backlog. Goodput counts only SLO-compliant
# tokens (best-effort lows carry no target, so they always comply);
# the EDF/FIFO ratio measures the scheduling mechanism, not host
# timing — both arms run identical seeded traffic on identical fleets.
# preempt_bitexact is the safety side of the same coin: a forced
# preempt/restore drain must be token-identical to an unpressured one.
OVR_REPLICAS, OVR_SLOTS, OVR_BLOCKS = 4, 2, 13  # 12 allocatable blocks
OVR_LOW, OVR_HIGH = 16, 8           # 16 lows on 8 slots = 2x oversub
OVR_GEN_LOW, OVR_GEN_HIGH = 48, 24  # highs are the long SLO-bearing work
OVR_HEAD_STEPS = 9                  # lows grown & pool full, THEN highs
OVR_MAXLEN = 64


def _overload_traffic(cfg):
    rng = np.random.RandomState(11)
    low = [(rng.randint(0, cfg.vocab_size,
                        size=int(rng.randint(5, 9))).astype(np.int32),
            OVR_GEN_LOW) for _ in range(OVR_LOW)]
    # long high prompts: first-token latency is prefill-dominated on
    # BOTH the loaded and unloaded fleet, so the TTFT comparison is
    # about queueing (what the scheduler controls), not prompt length
    high = [(rng.randint(0, cfg.vocab_size,
                         size=int(rng.randint(20, 25))).astype(np.int32),
             OVR_GEN_HIGH) for _ in range(OVR_HIGH)]
    return low, high


def _overload_fleet(cfg, params, scheduling):
    from repro.launch.router import ReplicaRouter

    replicas = [
        PagedContinuousBatchingServer(
            cfg, params, num_slots=OVR_SLOTS, max_len=OVR_MAXLEN,
            block_size=PAGED_BLOCK, prefill_chunk=PAGED_BLOCK,
            num_blocks=OVR_BLOCKS, segment=4, scheduling=scheduling)
        for _ in range(OVR_REPLICAS)
    ]
    return ReplicaRouter(replicas, policy="prefix", seed=3)


def _overload_drain(fleet, low, high, target):
    """Submit the low backlog, step until every slot is occupied and
    grown, then submit the highs mid-drain (the overload moment) and
    drain. Returns (wall, finished, high-priority fleet ids)."""
    t0 = time.perf_counter()
    for p, g in low:
        fleet.submit(p, g, priority=0)
    done = []
    for _ in range(OVR_HEAD_STEPS):
        done += fleet.step()
    hf = {fleet.submit(p, g, priority=1, ttft_target=target)
          for p, g in high}
    done += fleet.run()
    return time.perf_counter() - t0, done, hf


def _flush_fleet(fleet):
    """Force-evict every cached block (prefix index included) on every
    replica: each measured drain starts from the same cold pool the
    warmup saw, so warmup and measurement execute the same schedule and
    the measured run compiles nothing."""
    for r in fleet.replicas:
        r.mgr.alloc.evict_cached()


def _goodput(wall, done, high_fids, target):
    ok = sum(r.generated for r in done
             if r.rid not in high_fids or r.ttft <= target)
    return ok / wall


def _high_only_ttfts(fleet, high):
    """Unloaded-fleet TTFT for the high prompts. First tokens only
    materialize at segment boundaries, and an idle fleet uncaps its
    first segment to the whole remaining span — calibrating with the
    traffic's full gen would measure segment shape, not first-token
    latency. Same prompts, one-segment gen: the unloaded first
    boundary gets the same granularity the loaded fleet's capped
    segments have."""
    fids = {fleet.submit(p, 4, priority=1) for p, _ in high}
    return [r.ttft for r in fleet.run() if r.rid in fids]


def _preempt_bitexact(cfg, params):
    """Forced preempt/restore vs an unpressured pool, token-for-token,
    greedy and sampled rows in the same drain."""
    from repro.launch.sampling import SamplingParams

    rng = np.random.RandomState(3)
    reqs = [(rng.randint(0, cfg.vocab_size, size=6).astype(np.int32), 18)
            for _ in range(2)]
    samples = [None, SamplingParams(temperature=0.8, top_k=40, seed=13)]

    def drain(**kw):
        sched = PagedContinuousBatchingServer(
            cfg, params, num_slots=2, max_len=48, block_size=8,
            segment=4, **kw)
        for (p, g), sp in zip(reqs, samples):
            sched.submit(p, g, sp)
        return sched.run(), sched.stats

    ample, a_st = drain()               # default pool: no pressure
    tight, t_st = drain(num_blocks=6)   # two grown spans cannot coexist
    ok = (a_st.preemptions == 0 and t_st.preemptions > 0
          and t_st.restores > 0 and len(ample) == len(tight) == 2)
    for a, b in zip(sorted(ample, key=lambda r: r.rid),
                    sorted(tight, key=lambda r: r.rid)):
        ok = ok and np.array_equal(a.tokens, b.tokens)
    return float(ok)


def overload_rows():
    cfg = _continuous_cfg()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    low, high = _overload_traffic(cfg)

    edf = _overload_fleet(cfg, params, "edf")
    _overload_drain(edf, low, high, None)   # warmup: compile every shape
    _flush_fleet(edf)
    _high_only_ttfts(edf, high)             # warmup the unloaded shapes
    _flush_fleet(edf)
    ttfts_u = _high_only_ttfts(edf, high)   # measured: unloaded fleet
    p95_u = _pct(ttfts_u, 95)
    # the SLO is the acceptance bound itself: high-priority first tokens
    # within twice the unloaded fleet's p95
    target = 2.0 * p95_u
    _flush_fleet(edf)
    t0 = edf.stats.totals
    pre0, res0, stl0 = t0.preemptions, t0.restores, edf.stats.stolen
    wall_e, done_e, hf_e = _overload_drain(edf, low, high, target)
    good_e = _goodput(wall_e, done_e, hf_e, target)
    p95_e = _pct([r.ttft for r in done_e if r.rid in hf_e], 95)
    t1 = edf.stats.totals

    fifo = _overload_fleet(cfg, params, "fifo")
    _overload_drain(fifo, low, high, None)  # warmup
    _flush_fleet(fifo)
    wall_f, done_f, hf_f = _overload_drain(fifo, low, high, target)
    good_f = _goodput(wall_f, done_f, hf_f, target)
    p95_f = _pct([r.ttft for r in done_f if r.rid in hf_f], 95)

    bitexact = _preempt_bitexact(cfg, params)
    return [
        (f"serving/{ARCH}/overload/goodput_edf_tok_s",
         1e6 / max(good_e, 1e-9), good_e),
        (f"serving/{ARCH}/overload/goodput_fifo_tok_s",
         1e6 / max(good_f, 1e-9), good_f),
        (f"serving/{ARCH}/goodput_2x_over_fifo", 0.0,
         good_e / max(good_f, 1e-9)),
        (f"serving/{ARCH}/overload/high_ttft_p95_unloaded_s", 0.0, p95_u),
        (f"serving/{ARCH}/overload/high_ttft_p95_edf_s", 0.0, p95_e),
        (f"serving/{ARCH}/overload/high_ttft_p95_fifo_s", 0.0, p95_f),
        (f"serving/{ARCH}/overload/high_ttft_edf_over_2x_unloaded", 0.0,
         p95_e / max(2.0 * p95_u, 1e-9)),
        (f"serving/{ARCH}/overload/preemptions", 0.0,
         float(t1.preemptions - pre0)),
        (f"serving/{ARCH}/overload/restores", 0.0,
         float(t1.restores - res0)),
        (f"serving/{ARCH}/overload/stolen", 0.0,
         float(edf.stats.stolen - stl0)),
        (f"serving/{ARCH}/preempt_bitexact", 0.0, bitexact),
    ]


# RAG: the SAME paged scheduler, retrieval overlapped with decode (the
# default: the search starts on the I/O worker at submit and is
# collected after the next segment dispatch) vs the
# retrieve-then-decode pipeline (rag_overlap=False quiesces enqueued
# device work and searches inline on the dispatch thread). Retrieval
# cost is exact top-k scoring over the whole corpus plus a modeled
# 20ms payload fetch (io_latency_s — the disk/network stall a
# CPU-resident toy corpus doesn't otherwise exhibit; it sleeps with
# the GIL released, so the worker genuinely runs while the dispatch
# thread is inside XLA). Queries arrive in waves while long-running
# leads keep every slot decoding: the overlap arm's searches run
# behind the segment dispatch (synchronous on the CPU backend — the
# donated cache makes the seg() call block for the whole segment, a
# window far wider than one wave's retrieval), the serial arm stalls
# on every search before staging. Two scheduler properties carry the
# margin, and this row exists to catch regressions in them: (a) the
# submit-time kickoff onto the I/O worker, and (b) parked queries
# capping the next segment at ``segment`` steps (see
# ``_segment_steps``) so retrieved prompts stage at a near boundary
# instead of waiting out an uncapped power-of-two run — without the
# cap the overlap arm LOSES (admission latency eats more than
# retrieval hiding saves). The model is deliberately larger than the
# other serving rows' smoke cfg (d_model 512, 8 layers): the hiding
# window is the segment's compute, so it must cost real milliseconds.
# Lead lengths are staggered so retirements spread across boundaries
# and wave queries keep being admitted mid-flight. Queries concentrate
# on a few hot documents, so distinct queries retrieve overlapping
# chunk sets and the canonical-order pipeline turns that into
# chunk-block KV hits (the gated rag_chunk_hit_rate). Interleaved
# paired trials as in _measure_mix.
RAG_DOCS, RAG_DOC_LEN, RAG_HOT = 2048, 128, 4
RAG_IO_LATENCY = 0.020
RAG_LEAD_GENS = (72, 64, 56, 48)        # one per slot, staggered
RAG_WAVES, RAG_PER_WAVE, RAG_WAVE_GEN = 8, 2, 12


def _rag_cfg():
    # beefed-up smoke config: enough per-step compute that a segment's
    # in-flight window is worth hiding retrieval behind
    return dataclasses.replace(_continuous_cfg(), d_model=512,
                               num_heads=8, num_kv_heads=2,
                               d_ff=2048, num_layers=8)


def _rag_setup(cfg):
    from repro.retrieval import ChunkedCorpus, EmbeddingIndex, RagPipeline
    from repro.retrieval import make_toy_corpus

    docs = make_toy_corpus(cfg.vocab_size, n_docs=RAG_DOCS,
                           doc_len=RAG_DOC_LEN, seed=0)
    corpus = ChunkedCorpus(docs, chunk_tokens=2 * PAGED_BLOCK)
    index = EmbeddingIndex(corpus, vocab_size=cfg.vocab_size, seed=0,
                           io_latency_s=RAG_IO_LATENCY)
    pipe = RagPipeline(index, system_prefix=list(range(5, 5 + PAGED_BLOCK)),
                       block_size=PAGED_BLOCK, top_k=2)
    rng = np.random.RandomState(7)

    def q(i):
        d = docs[int(rng.randint(RAG_HOT))]
        lo = int(rng.randint(0, d.size - 8))
        return d[lo:lo + 4 + (i % 3)].copy()

    leads = [q(i) for i in range(len(RAG_LEAD_GENS))]
    waves = [[q(w * RAG_PER_WAVE + j) for j in range(RAG_PER_WAVE)]
             for w in range(RAG_WAVES)]
    return pipe, leads, waves


def rag_rows():
    cfg = _rag_cfg()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    pipe, leads, waves = _rag_setup(cfg)
    useful = (sum(RAG_LEAD_GENS)
              + RAG_WAVES * RAG_PER_WAVE * RAG_WAVE_GEN)
    max_len = pipe.prompt_len_for + 8 + max(RAG_LEAD_GENS)

    def make(overlap):
        return PagedContinuousBatchingServer(
            cfg, params, num_slots=CONT_SLOTS, max_len=max_len,
            block_size=PAGED_BLOCK, prefill_chunk=PAGED_BLOCK, segment=8,
            rag=pipe, rag_overlap=overlap)

    def run(server):
        # leads first (one per slot) so every wave lands mid-decode
        # with zero free slots; the drive is identical in both arms,
        # only where each wave's retrieval stall lands differs
        t0 = time.perf_counter()
        for q, g in zip(leads, RAG_LEAD_GENS):
            server.submit_query(q, g)
        server.step()
        for wave in waves:
            for q in wave:
                server.submit_query(q, RAG_WAVE_GEN)
            server.step()
        server.run()
        return time.perf_counter() - t0

    over, serial = make(True), make(False)
    for _ in range(2):          # compile + cover both segment shapes
        run(over), run(serial)
    hits0 = over.stats.retrieval_chunk_hits     # measured trials only
    blocks0 = over.stats.retrieval_chunk_blocks
    ratios, ov, se = [], [], []
    for _ in range(PAGED_TRIALS):
        ow = run(over)
        sw = run(serial)
        ratios.append(sw / ow)
        ov.append(useful / ow)
        se.append(useful / sw)
    mid = int(np.argsort(ratios)[len(ratios) // 2])
    hit_rate = (over.stats.retrieval_chunk_hits - hits0) / max(
        over.stats.retrieval_chunk_blocks - blocks0, 1)
    return [
        (f"serving/{ARCH}/rag/tok_s", 1e6 / ov[mid], ov[mid]),
        (f"serving/{ARCH}/rag_serial/tok_s", 1e6 / se[mid], se[mid]),
        (f"serving/{ARCH}/rag_overlap_over_serial", 0.0, ratios[mid]),
        (f"serving/{ARCH}/rag_chunk_hit_rate", 0.0, hit_rate),
        (f"serving/{ARCH}/rag/overlap_frac", 0.0,
         over.stats.retrieval_overlap_frac),
    ]


def rows():
    return (loop_vs_scan_rows() + flat_vs_plan_rows() + continuous_rows()
            + paged_rows() + paged_kernel_rows() + mesh_rows()
            + router_rows() + spec_rows() + overload_rows() + rag_rows())
