"""End-to-end serving driver (the paper is an inference paper).

Static-batch mode: bucket prompts, prefill once, scan-compiled greedy
decode of N tokens in ONE dispatch (``--decode loop`` keeps the PR-2
per-token loop for comparison). ``--pipeline-depths 2,4`` builds a
per-layer ``ExecutionPlan`` (layer i gets depth[i % len]) so different
layers trace different sidebar kernel variants.

Continuous mode (``--continuous``): mixed-length traffic through the
slot scheduler — bucketed admission into freed slots between scan-
compiled decode segments, one persistent slot KV cache, and an
executable cache keyed by (bucket, plan).

Run: PYTHONPATH=src python examples/serve_batch.py --arch deepseek-7b \
         --batch 4 --prompt-len 32 --gen 16 \
         --execution-mode sidebar_pipelined --pipeline-depth 4
     PYTHONPATH=src python examples/serve_batch.py --continuous \
         --requests 8 --slots 4 --segment 8
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfglib
from repro.core.modes import ExecutionMode, ExecutionPlan, LayerPlan
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.sampling import SamplingParams
from repro.launch.scheduler import (
    ContinuousBatchingServer,
    PagedContinuousBatchingServer,
)
from repro.launch.serve import Server
from repro.models.registry import get_model


def device_desc() -> str:
    """Where this run executes, as JAX reports it."""
    dev = jax.devices()[0]
    return f"{dev.platform} {dev.device_kind} x{len(jax.devices())}"


def build_sampling(args) -> SamplingParams | None:
    temperature = args.temperature
    if temperature is None:
        if args.top_k is None and args.top_p is None:
            return None  # no sampling flags at all -> greedy
        temperature = 1.0  # top-k/top-p imply sampling
    return SamplingParams(temperature=temperature, top_k=args.top_k,
                          top_p=args.top_p, seed=args.seed)


def build_faults(args):
    """``--faults site=rate,...`` -> a seeded ``FaultInjector`` (sites:
    alloc, evict_storm, stage_stall — see launch/faults.py)."""
    if not args.faults:
        return None
    from repro.launch.faults import FaultInjector

    rates = {}
    for part in args.faults.split(","):
        site, rate = part.split("=")
        rates[site.strip()] = float(rate)
    return FaultInjector(seed=args.fault_seed, rates=rates,
                         max_per_site=args.max_faults_per_site)


def build_spec(args, cfg, params, api):
    """``--spec-k K`` -> a ``SpecConfig`` (K drafted tokens per row per
    step). ``--draft-arch`` picks the draft model; the default (unset)
    is the ORACLE draft — the target itself drafts, so greedy
    acceptance is exactly 1.0 and the run measures pure
    draft+verify overhead. Output tokens are bit-identical to plain
    decode either way; only throughput depends on the draft."""
    if not args.spec_k:
        return None
    from repro.launch.spec import SpecConfig

    if args.draft_arch is None:
        return SpecConfig(draft_cfg=cfg, draft_params=params, k=args.spec_k)
    draft_cfg = cfglib.get_smoke_config(args.draft_arch)
    draft_api = get_model(draft_cfg)
    draft_params = draft_api.init(jax.random.PRNGKey(1), draft_cfg)
    return SpecConfig(draft_cfg=draft_cfg, draft_params=draft_params,
                      k=args.spec_k)


def build_mesh(args):
    """``--mesh RxC`` (or RxCxP) -> a canonical serving mesh; the
    "model" (last) axis is the tensor-parallel degree. Run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to get more
    than one CPU device."""
    if not args.mesh:
        return None
    from repro.launch.mesh import make_serving_mesh

    shape = tuple(int(d) for d in args.mesh.lower().split("x"))
    return make_serving_mesh(shape)


def build_plan(args, cfg):
    mode = ExecutionMode(args.execution_mode)
    if args.pipeline_depths:
        depths = [int(d) for d in args.pipeline_depths.split(",")]
        per_layer = [
            LayerPlan(ExecutionMode.SIDEBAR_PIPELINED,
                      depth=depths[i % len(depths)])
            for i in range(cfg.num_layers)
        ]
        return ExecutionPlan.by_index(per_layer)
    return LayerPlan(mode, depth=args.pipeline_depth)


def run_static(args, cfg, api, params, plan):
    sample = build_sampling(args)
    print(f"arch={cfg.arch_id} (smoke config, {device_desc()}), "
          f"batch={args.batch}, prompt={args.prompt_len}, gen={args.gen}, "
          f"plan={plan}, decode={args.decode}, sample={sample}")
    server = Server(cfg, params, max_len=args.prompt_len + args.gen,
                    plan=plan, mesh=build_mesh(args))
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size, dtype=jnp.int32,
    )
    extra = {}
    if cfg.family == "audio":
        extra["frames"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.encoder_seq, cfg.d_model), cfg.dtype)
    if cfg.family == "vlm":
        extra["image_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.num_image_tokens, cfg.d_model), cfg.dtype)

    # warmup (compile) — same gen length so the timed call reuses the
    # cached N-step scan executable instead of tracing it
    server.generate(prompts, args.gen, extra, decode=args.decode,
                    sample=sample)
    t0 = time.perf_counter()
    result = server.generate(prompts, args.gen, extra, decode=args.decode,
                             sample=sample)
    dt = time.perf_counter() - t0
    total_new = args.batch * args.gen
    print(f"generated {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s on {device_desc()})")
    print("sample continuation ids:",
          result.tokens[0, args.prompt_len:args.prompt_len + 8].tolist())


def run_continuous(args, cfg, api, params, plan):
    sample = build_sampling(args)
    mesh = build_mesh(args)
    max_len = args.prompt_len + args.gen
    spec = build_spec(args, cfg, params, api)
    if spec is not None and not args.paged:
        raise SystemExit("--spec-k requires --paged (the verifier runs "
                         "through the block pool)")
    if args.paged:
        # block_size must divide max_len; snap to the nearest divisor
        bs = args.block_size
        while max_len % bs:
            bs -= 1
        sched = PagedContinuousBatchingServer(
            cfg, params, num_slots=args.slots, max_len=max_len,
            block_size=bs, prefill_chunk=args.prefill_chunk,
            segment=args.segment, plan=plan, kernel=args.kernel,
            mesh=mesh, spec=spec,
        )
        kind = f"paged (block_size={bs}, kernel={args.kernel}"
        if spec is not None:
            kind += (f", spec k={spec.k} "
                     f"draft={spec.draft_cfg.arch_id}"
                     f"{' (oracle)' if args.draft_arch is None else ''}")
        kind += ")"
    else:
        sched = ContinuousBatchingServer(
            cfg, params, num_slots=args.slots, max_len=max_len,
            buckets=(args.prompt_len // 2, args.prompt_len),
            segment=args.segment, plan=plan, mesh=mesh,
        )
        kind = "slab"
    if mesh is not None:
        kind += (f", mesh={'x'.join(map(str, mesh.devices.shape))} "
                 f"{tuple(mesh.axis_names)}")
    print(f"arch={cfg.arch_id} continuous [{kind}]: "
          f"requests={args.requests}, slots={args.slots}, "
          f"segment={args.segment}, plan={plan}, sample={sample}")
    rng = np.random.RandomState(0)
    # paged traffic carries a shared prefix (the chat system-prompt
    # shape) so the smoke exercises prefix-cache splicing; slab traffic
    # keeps the full [2, prompt_len) length spread so BOTH admission
    # buckets stay covered
    prefix = rng.randint(0, cfg.vocab_size, size=args.prompt_len // 2)
    useful = 0
    for i in range(args.requests):
        gen = int(rng.randint(1, args.gen))
        useful += gen
        if args.paged:
            tail = int(rng.randint(2, max(3, args.prompt_len // 2)))
            prompt = np.concatenate(
                [prefix, rng.randint(0, cfg.vocab_size, size=tail)])
        else:
            plen = int(rng.randint(2, args.prompt_len))
            prompt = rng.randint(0, cfg.vocab_size, size=plen)
        # alternate sampled/greedy rows so the smoke covers the mixed
        # segment program when sampling flags are given
        sched.submit(prompt, gen, sample=sample if i % 2 == 0 else None)
    t0 = time.perf_counter()
    done = sched.run()
    dt = time.perf_counter() - t0
    print(f"drained {len(done)} requests / {useful} tokens in {dt:.2f}s "
          f"({useful/dt:.1f} tok/s on {device_desc()}, cold)")
    # the executable-cache counters are THE re-trace regression signal:
    # repeat traffic of a shape/plan already served must be all hits, so
    # a compile count that grows run-over-run in the CI smoke log means
    # something started re-tracing; the paged lines add pool occupancy
    # and the prefix hit rate (> 0 expected on this shared-prefix mix)
    print(sched.stats.summary())
    print("executables:", [k[:3] for k in sched.executable_cache_keys()])
    if args.paged:
        # the shared prefix spans >= one full block, so the index MUST
        # be consulted and MUST hit — a vacuously-passing guard here
        # would let a dead prefix cache through the CI smoke
        assert sched.stats.prefix_block_lookups > 0, (
            "paged smoke never consulted the prefix index"
        )
        if args.requests >= 3:  # enough traffic behind the first admits
            assert sched.stats.prefix_block_hits > 0, (
                "shared-prefix smoke produced zero prefix-cache hits"
            )
    if spec is not None:
        # the speculative smoke's contract: it actually speculated, the
        # pool drained clean, and an oracle draft was always accepted
        assert sched.stats.spec_steps > 0, "spec run never speculated"
        assert sched.mgr.alloc.in_use == 0, "spec run leaked pool blocks"
        if args.draft_arch is None and args.temperature is None:
            assert sched.stats.spec_acceptance_rate == 1.0, (
                "greedy oracle draft must be fully accepted, got "
                f"{sched.stats.spec_acceptance_rate:.2f}"
            )


def run_rag(args, cfg, api, params, plan):
    """The CI RAG smoke: shared-corpus multi-turn traffic through
    ``submit_query``. Retrieval runs as a host-side flexible op between
    segment dispatches (overlapped with in-flight decode by default),
    the pipeline assembles block-aligned prompts, and distinct queries
    that retrieve the same chunks splice each other's chunk-addressed
    KV blocks. Asserts the reuse is real: nonzero chunk-level cache
    hits, every query drained, pool clean."""
    from repro.retrieval import ChunkedCorpus, EmbeddingIndex, RagPipeline
    from repro.retrieval import make_toy_corpus

    sample = build_sampling(args)
    max_len = args.prompt_len + args.gen
    bs = args.block_size
    while max_len % bs:
        bs -= 1
    chunk_tokens = args.chunk_tokens or bs
    if chunk_tokens % bs:
        raise SystemExit(f"--chunk-tokens {chunk_tokens} must be a "
                         f"multiple of the pool block size {bs}")
    docs = make_toy_corpus(cfg.vocab_size, n_docs=args.corpus_size,
                           doc_len=max(2 * chunk_tokens, 32),
                           seed=args.seed)
    corpus = ChunkedCorpus(docs, chunk_tokens=chunk_tokens)
    index = EmbeddingIndex(corpus, vocab_size=cfg.vocab_size,
                           seed=args.seed)
    rag = RagPipeline(index, system_prefix=list(range(5, 5 + bs // 2)),
                      block_size=bs, top_k=args.rag_top_k)
    sched = PagedContinuousBatchingServer(
        cfg, params, num_slots=args.slots, max_len=max_len,
        block_size=bs, prefill_chunk=args.prefill_chunk,
        segment=args.segment, plan=plan, kernel=args.kernel,
        mesh=build_mesh(args), rag=rag,
    )
    print(f"arch={cfg.arch_id} rag [paged, block_size={bs}, "
          f"kernel={args.kernel}]: corpus={args.corpus_size} docs x "
          f"{len(corpus.chunks)} chunks ({chunk_tokens} tok), "
          f"top_k={args.rag_top_k}, queries={args.requests}, "
          f"slots={args.slots}, sample={sample}")
    rng = np.random.RandomState(args.seed)
    # multi-turn traffic over a SHARED corpus: queries concentrate on a
    # few documents so distinct turns retrieve overlapping chunk sets —
    # the canonical-order pipeline turns that overlap into shared
    # leading block runs the pool can splice
    hot = max(1, args.corpus_size // 2)
    useful = 0
    for i in range(args.requests):
        d = docs[rng.randint(hot)]
        lo = int(rng.randint(0, d.size - 6))
        q = d[lo:lo + int(rng.randint(3, 7))]
        gen = int(rng.randint(1, args.gen))
        useful += gen
        sched.submit_query(q, gen, sample=sample if i % 2 == 0 else None)
    t0 = time.perf_counter()
    done = sched.run()
    dt = time.perf_counter() - t0
    print(f"drained {len(done)} requests / {useful} tokens in {dt:.2f}s "
          f"({useful/dt:.1f} tok/s on {device_desc()}, cold)")
    print(sched.stats.summary())
    # the smoke's contract: every query retrieved and drained, the
    # shared corpus produced real chunk-level KV reuse (a zero here
    # means content addressing is dead), and the pool came back clean
    assert len(done) == args.requests, (
        f"drain lost requests: {len(done)} != {args.requests}")
    assert sched.stats.retrievals == args.requests
    assert sched.stats.retrieval_chunk_blocks > 0
    if args.requests >= 3:  # enough turns behind the first admits
        assert sched.stats.retrieval_chunk_hits > 0, (
            "shared-corpus RAG smoke produced zero chunk-cache hits"
        )
    assert sched.mgr.alloc.in_use == 0, "RAG run leaked pool blocks"


def run_overload(args, cfg, api, params, plan):
    """The CI overload smoke: 2x-oversubscribed priority traffic on a
    deliberately tiny paged pool (optionally with seeded fault
    injection). A low-priority backlog saturates every slot; high-
    priority requests land mid-drain and jump it via EDF admission +
    preemption (spill to the sidebar region, restore later). Asserts
    the robustness invariants end to end: every request completes and
    the pool drains with zero leaked blocks."""
    faults = build_faults(args)
    max_len = args.prompt_len + args.gen
    bs = args.block_size
    while max_len % bs:
        bs -= 1
    sched = PagedContinuousBatchingServer(
        cfg, params, num_slots=args.slots, max_len=max_len,
        block_size=bs, prefill_chunk=args.prefill_chunk,
        num_blocks=args.num_blocks, segment=args.segment, plan=plan,
        kernel=args.kernel, faults=faults, scheduling="edf",
    )
    pool_str = (f"{args.num_blocks} blocks" if args.num_blocks
                else "default pool")
    print(f"arch={cfg.arch_id} overload [paged, {pool_str}, "
          f"block_size={bs}]: slots={args.slots}, "
          f"faults={args.faults or 'none'} (seed={args.fault_seed})")
    rng = np.random.RandomState(args.seed)
    n_low = 2 * args.slots                  # 2x oversubscription
    n_high = max(1, args.slots // 2)
    for _ in range(n_low):
        p = rng.randint(0, cfg.vocab_size,
                        size=max(2, args.prompt_len // 4))
        sched.submit(p, args.gen, priority=0)
    done = sched.step()                     # backlog mid-flight ...
    for _ in range(n_high):                 # ... then the highs land
        p = rng.randint(0, cfg.vocab_size,
                        size=max(2, args.prompt_len - 1))
        sched.submit(p, max(2, args.gen // 2), priority=1,
                     ttft_target=60.0)
    t0 = time.perf_counter()
    done += sched.run()
    dt = time.perf_counter() - t0
    print(f"drained {len(done)} requests in {dt:.2f}s (cold)")
    print(sched.stats.summary())
    if faults is not None:
        print(f"faults injected: {faults.total_injected} "
              f"({dict(faults.injected)})")
    # the smoke's contract: everything completes, nothing leaks
    assert len(done) == n_low + n_high, (
        f"drain lost requests: {len(done)} != {n_low + n_high}")
    assert sched.mgr.alloc.in_use == 0, "pool leaked blocks"
    assert (sched.mgr.alloc.num_free + sched.mgr.alloc.num_evictable
            == sched.mgr.alloc.capacity), "pool accounting drifted"
    assert len(sched.spill) == 0 and sched.spill.in_use_bytes == 0, (
        "spill region holds payloads after a full drain")
    if args.num_blocks:  # tiny pool: overload must actually preempt
        assert sched.stats.preemptions > 0, (
            "tiny-pool overload smoke never preempted"
        )
        assert sched.stats.restores > 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b", choices=cfglib.ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument(
        "--execution-mode", default="sidebar",
        choices=[ExecutionMode.SIDEBAR.value,
                 ExecutionMode.SIDEBAR_PIPELINED.value],
        help="sidebar kernel variant backing the fused MLP ops",
    )
    ap.add_argument(
        "--pipeline-depth", type=int, default=2,
        help="VMEM ring depth T for sidebar_pipelined (>= 1)",
    )
    ap.add_argument(
        "--pipeline-depths", default=None,
        help="comma list of per-layer ring depths -> heterogeneous "
             "ExecutionPlan (layer i gets depths[i %% len]); unrolls the "
             "layer stack so each layer traces its own kernel variant",
    )
    ap.add_argument(
        "--decode", default="scan", choices=["scan", "loop"],
        help="scan: N tokens in one compiled program; loop: PR-2 "
             "one-dispatch-per-token baseline",
    )
    ap.add_argument("--continuous", action="store_true",
                    help="mixed-length traffic through the slot scheduler")
    ap.add_argument("--paged", action="store_true",
                    help="with --continuous: serve through the paged KV "
                         "pool (block tables, prefix caching, chunked "
                         "prefill-ahead)")
    ap.add_argument("--kernel", default="paged",
                    choices=["paged", "slab"],
                    help="with --paged: 'paged' decodes in place on the "
                         "block pool (table-walking attention, no "
                         "gather/scatter); 'slab' keeps the dense "
                         "round-trip reference segment")
    ap.add_argument("--use-pallas", action="store_true",
                    help="enable the Pallas kernels (interpret mode on the "
                         "CPU) — CI's paged-attention kernel smoke")
    ap.add_argument("--block-size", type=int, default=8,
                    help="KV pool block size in token positions")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size in blocks (default: enough that "
                         "no request ever waits; set it small to force "
                         "preemption under load)")
    ap.add_argument("--overload", action="store_true",
                    help="overload smoke: 2x-oversubscribed priority "
                         "traffic on the paged server — low-priority "
                         "backlog, high-priority arrivals mid-drain, "
                         "EDF admission + preemption; asserts zero "
                         "leaks and full completion")
    ap.add_argument("--faults", default=None,
                    help="seeded fault injection, 'site=rate,...' "
                         "(sites: alloc, evict_storm, stage_stall), "
                         "e.g. --faults alloc=0.1,evict_storm=0.1")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--max-faults-per-site", type=int, default=8,
                    help="bound Bernoulli firings per site so a drain "
                         "terminates even at rate 1.0")
    ap.add_argument("--rag", action="store_true",
                    help="RAG smoke: shared-corpus multi-turn queries "
                         "through submit_query — host-side retrieval "
                         "between segment dispatches, chunk-addressed "
                         "KV splicing; asserts nonzero chunk-cache hits")
    ap.add_argument("--corpus-size", type=int, default=4,
                    help="with --rag: number of documents in the toy "
                         "corpus (queries concentrate on the first half)")
    ap.add_argument("--rag-top-k", type=int, default=2,
                    help="with --rag: retrieved chunks per query "
                         "(--top-k is the SAMPLING top-k; the retrieval "
                         "fan-in lives here)")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="with --rag: corpus chunk length in tokens; "
                         "must be a multiple of the pool block size "
                         "(default: one block)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prefill-ahead chunk length (default block size)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft K tokens per row "
                         "per step and verify them in one batched "
                         "program (0 disables; requires --paged); "
                         "output tokens stay bit-identical to plain "
                         "decode regardless of the draft model")
    ap.add_argument("--draft-arch", default=None, choices=cfglib.ARCH_IDS,
                    help="draft model architecture for --spec-k "
                         "(default: the target itself — the 'oracle' "
                         "draft with greedy acceptance 1.0, measuring "
                         "pure speculation overhead)")
    ap.add_argument("--mesh", default=None,
                    help="serving mesh shape 'DATAxMODEL' (e.g. 1x2): "
                         "continuous serving runs tensor-parallel over "
                         "the mesh's 'model' axis via shard_map")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--segment", type=int, default=8)
    ap.add_argument(
        "--temperature", type=float, default=None,
        help="enable sampled decoding (temperature 0 = exact greedy); "
             "continuous mode samples every other request",
    )
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--top-p", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling seed (same seed => same tokens)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = cfglib.get_smoke_config(args.arch)
    if args.use_pallas:
        cfg = dataclasses.replace(cfg, use_pallas=True)
    api = get_model(cfg)
    plan = build_plan(args, cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    if args.rag:
        run_rag(args, cfg, api, params, plan)
    elif args.overload:
        run_overload(args, cfg, api, params, plan)
    elif args.continuous:
        run_continuous(args, cfg, api, params, plan)
    else:
        run_static(args, cfg, api, params, plan)


if __name__ == "__main__":
    main()
