"""Continuous batching: slot-based KV cache + segment-synchronous admission.

The PR-2 ``Server`` is a static-batch driver: every ``generate`` call
allocates a fresh KV cache, and a request that finishes early keeps its
batch row busy until the whole batch drains. This module adds the serving
discipline the ROADMAP's "heavy traffic" north star actually needs:

  * **Slot cache** — ONE persistent KV cache with ``num_slots`` batch
    rows, allocated once. Each request owns a slot for its lifetime; a
    freed slot is overwritten wholesale by the next admission (so no
    cross-request state leaks, for attention and recurrent caches alike).
    The batch axis of every cache leaf is *probed*, not assumed: specs
    for batch=2 vs batch=3 are diffed, which keeps the scheduler family-
    agnostic about cache layouts (GQA 5-D KV, MLA latent, int8 scales).
  * **Prompt bucketing + batched admission** — one admission round
    prefills EVERY co-admitted prompt's ``prompt[:-1]`` together, right-
    padded to the round's largest bucket, then runs ONE single-token
    decode of each true last prompt token at its true per-row position
    (the same rowwise-position machinery as segment decode), then
    scatters all rows into the slot cache in one insert. The correction
    step overwrites the first pad's KV slot and returns the first
    generated token from the right logits row, so bucketing never
    changes tokens: pad KV beyond the true length is overwritten by
    later decode writes or masked by the causal ``kpos <= pos``
    attention mask.
  * **Segment decode** — between admissions, ALL occupied slots advance
    ``segment`` tokens in ONE batched scan-compiled dispatch: the serve
    step runs over the whole slot cache with a per-row ``(B,)`` position
    vector threaded down to the attention math (RoPE, causal mask, and
    KV writes all key off each row's own position — see
    ``models.attention.rowwise_pos``). This keeps the matmuls dense over
    slots instead of vmapping into ``num_slots`` batch-1 programs with
    scatter KV writes (the "vmap tax" that made continuous batching lose
    to static batching at smoke scale). When every slot is occupied at
    the SAME position the scheduler dispatches the aligned fast path — a
    scalar-position program whose KV write is one dense
    ``dynamic_update_slice``, exactly like ``serve.make_decode_scan``.
    Requests finish mid-batch without stalling neighbours; their slots
    re-enter the free list at the next segment boundary.
  * **Sampling** — ``submit(..., sample=SamplingParams(...))`` gives a
    request temperature / top-k / top-p decoding. The request's PRNG
    stream is position-keyed (``launch.sampling``): its base key lives
    in the slot state and the token at sequence index p is keyed by
    (base key, p), so admission order, slot churn, segment length, and
    even a scheduler restart mid-stream (resubmit prompt + tokens-so-far
    with the same seed) never change the stream. Greedy and sampled
    requests share one batched segment program: greedy rows carry
    temperature 0, which is exact argmax.
  * **Executable cache** — every compiled program is keyed by
    ``(kind, shape-key, plan)``: repeat traffic (same bucket, same plan)
    never re-traces. ``stats["compiles"]`` / ``stats["hits"]`` make the
    no-retrace property testable.

Scope: families whose decode is batch-row independent and memory-free
(``dense`` — GQA and MLA — and ``moe``). Audio/VLM need per-request
encoder memory threaded through admission; that is an open item. MoE
caveat: pad tokens in a bucketed prefill compete for expert capacity, so
under a dropping ``capacity_factor`` a padded prefill can route real
tokens differently than an exact-length one — serve MoE with a no-drop
capacity factor (or exact-fit buckets) when bit-parity with solo decode
matters.

``PagedContinuousBatchingServer`` (below) swaps the slab cache for the
block-granular paged KV pool of ``launch.kvpool`` — prefix caching,
chunked prefill-ahead, and admission fused into the segment program —
with the same external contract and bit-identical tokens.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.modes import (
    ExecutionMode,
    ExecutionPlan,
    LayerPlan,
    coerce_layer_plan,
)
from repro.core.sidebar import SidebarSpillRegion
from repro.ft.watchdog import SegmentWatchdog
from repro.kernels import ops as kops
from repro.launch import kvpool as kvp
from repro.launch import sampling
from repro.launch.faults import FaultInjector
from repro.launch.sampling import SamplingParams
from repro.launch.serve import (
    PER_LAYER_PLAN_FAMILIES,
    make_prefill_step,
    make_serve_step,
    make_tp_spec,
    make_verify_step,
)
from repro.launch.spec import SpecConfig, accepted_prefix, make_draft_program
from repro.launch.tracing import Tracer
from repro.models import layers as L
from repro.models.registry import get_model

Array = jax.Array

# memory-free, batch-row-independent decode — currently the same set
# whose stacks realize per-layer plans, so the constant is shared
_SUPPORTED_FAMILIES = PER_LAYER_PLAN_FAMILIES

DEFAULT_BUCKETS = (16, 32, 64, 128)


@dataclasses.dataclass(frozen=True)
class FinishedRequest:
    """One drained request: the prompt plus every generated token.

    ``ttft`` and ``itl`` are dispatch times on the scheduler's clock:
    from submit to the dispatch of the program that produced the first
    token, and the mean gap between dispatches after it. On an
    asynchronous device a dispatch is the enqueue, not the moment the
    token reaches the host."""

    rid: int
    prompt: np.ndarray        # (S,) int32 — as submitted
    tokens: np.ndarray        # (generated,) int32
    prompt_len: int
    generated: int
    ttft: float = float("nan")   # submit -> first-token dispatch (s)
    itl: float = float("nan")    # mean gap between dispatches (s)


@dataclasses.dataclass(eq=False)
class _Request:
    """One submitted request while it waits (pending / staging / spilled).

    ``priority`` is the class (higher wins); ``ttft_target`` makes the
    EDF deadline (``submit_t + ttft_target``; no target = deadline inf,
    i.e. best-effort); ``itl_target`` is recorded for per-class stats.
    ``seq`` is the arrival index — the final tie-break, which makes
    every scheduling score a strict total order (no thrash: a victim is
    always *strictly* worse than the request it yields to)."""

    rid: int
    prompt: np.ndarray
    max_new: int
    sample: SamplingParams | None
    priority: int = 0
    ttft_target: float | None = None
    itl_target: float | None = None
    submit_t: float = 0.0
    seq: int = 0

    @property
    def deadline(self) -> float:
        return (math.inf if self.ttft_target is None
                else self.submit_t + self.ttft_target)


@dataclasses.dataclass
class _Slot:
    rid: int | None = None
    pos: int = 0              # next KV write position (= current length)
    remaining: int = 0
    generated: int = 0        # tokens produced so far (host-side count)
    # generated tokens as (device_array, row, take) chunk handles — the
    # async drain loop never syncs token VALUES; chunks materialize to
    # numpy only when a request is handed back (see _materialize)
    chunks: list[tuple] = dataclasses.field(default_factory=list)
    prompt: np.ndarray | None = None
    sample: SamplingParams | None = None
    # the request's PRNG base key ((2,) uint32): position-keyed at use,
    # so the stream survives slot churn and scheduler restarts
    key: np.ndarray | None = None
    req: _Request | None = None
    first_t: float | None = None   # first-token dispatch time (TTFT)

    @property
    def free(self) -> bool:
        return self.rid is None


# per-leaf batch-axis probing now lives with the paged pool (which also
# probes length axes); re-exported here for existing callers/tests
probe_batch_axes = kvp.probe_batch_axes


@dataclasses.dataclass
class SchedulerStats:
    """Typed scheduler counters (one object, attribute access; indexing
    kept as a compatibility shim for older call sites).

    Executable-cache counters (``compiles``/``hits``) are THE re-trace
    regression signal; ``wasted_steps`` counts free/dead slot rows the
    batched segment programs decode alongside active ones; the pool/
    prefix fields are live only on the paged scheduler, as are the
    robustness counters (``preemptions``/``restores``/``unstaged``/
    ``spilled_blocks``...) and the speculative-decoding group
    (``spec_steps``/``spec_drafted``/``spec_accepted``/
    ``spec_commit_copies``, with ``spec_acceptance_rate`` derived).
    ``compile_calls`` counts program calls that traced and compiled (or
    loaded) a program ``_compiled`` had just built, the calls under a
    ``compile`` span; ``retire_waits`` counts ``_materialize`` calls
    that found a finished request's token buffer not yet computed
    (``is_ready()`` false), so the host copy waited for the device: a
    drained pipeline.
    Under speculation ``decode_steps`` still counts EMITTED tokens
    (1..k+1 per row per verify) and ``wasted_steps`` absorbs the
    rejected remainder, so throughput accounting stays comparable with
    plain decode. ``summary()`` renders the lot for smoke logs.
    """

    # executable cache
    compiles: int = 0
    hits: int = 0
    compile_calls: int = 0
    # admission / decode
    admitted: int = 0
    segments: int = 0
    decode_steps: int = 0
    wasted_steps: int = 0
    admit_deferrals: int = 0
    retire_waits: int = 0
    # paged pool (PagedContinuousBatchingServer only)
    stage_chunks: int = 0
    stage_stalls: int = 0
    cow_copies: int = 0
    evictions: int = 0
    prefix_block_lookups: int = 0
    prefix_block_hits: int = 0
    prefix_prompt_blocks: int = 0   # full prompt[:-1] blocks walked
    chunk_interior_hits: int = 0    # splices past the first miss
    pool_blocks: int = 0
    pool_in_use: int = 0
    pool_in_use_peak: int = 0
    # overload robustness (preemption / cancel / watchdog)
    preemptions: int = 0       # active slots spilled to the host region
    restores: int = 0          # spilled requests spliced back and resumed
    unstaged: int = 0          # staging entries reclaimed back to pending
    spilled_blocks: int = 0
    restored_blocks: int = 0
    cancelled: int = 0
    watchdog_events: int = 0   # segments past k * median segment wall
    # speculative decoding (PagedContinuousBatchingServer(spec=...) only)
    spec_steps: int = 0        # draft+verify scheduler iterations
    spec_drafted: int = 0      # draft tokens submitted to the verifier
    spec_accepted: int = 0     # of those, accepted (matched the target)
    spec_commit_copies: int = 0  # scratch->pool block copies (accepted KV)
    # retrieval stage (PagedContinuousBatchingServer(rag=...) only)
    retrievals: int = 0             # queries assembled by the pipeline
    retrieval_overlapped: int = 0   # of those, hidden behind a dispatch
    retrieval_chunk_blocks: int = 0  # retrieved-chunk blocks staged
    retrieval_chunk_hits: int = 0    # of those, spliced from the index

    def __getitem__(self, key: str) -> int:
        return getattr(self, key)

    def __setitem__(self, key: str, value: int) -> None:
        setattr(self, key, value)

    @property
    def exec_hit_rate(self) -> float:
        return self.hits / max(self.compiles + self.hits, 1)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of full prompt blocks served from the index —
        hit blocks over prompt blocks WALKED, not over lookups issued.
        The old lookups-based denominator undercounted the miss side
        whenever the walk stopped early (and with interior-hole
        splicing the walk never stops early, so lookups ≈ walked and
        the two now differ only in old recorded data)."""
        return self.prefix_block_hits / max(self.prefix_prompt_blocks, 1)

    @property
    def retrieval_chunk_hit_rate(self) -> float:
        """Fraction of retrieved-chunk blocks spliced from the KV index
        rather than prefilled — the chunk-sharing payoff metric."""
        return (self.retrieval_chunk_hits
                / max(self.retrieval_chunk_blocks, 1))

    @property
    def retrieval_overlap_frac(self) -> float:
        """Fraction of retrievals that ran while a decode segment was
        in flight (their host time hidden behind accelerator work)."""
        return self.retrieval_overlapped / max(self.retrievals, 1)

    @property
    def pool_occupancy(self) -> float:
        return self.pool_in_use / max(self.pool_blocks, 1)

    @property
    def wasted_step_frac(self) -> float:
        return self.wasted_steps / max(self.decode_steps, 1)

    @property
    def spec_acceptance_rate(self) -> float:
        """Fraction of drafted tokens the target accepted. 1.0 means
        every draft guessed the target's position-keyed token (the
        oracle-draft ceiling); output correctness never depends on this
        number — only throughput does."""
        return self.spec_accepted / max(self.spec_drafted, 1)

    def summary(self) -> str:
        """One printable line per concern — the serving example's stats
        report."""
        lines = [
            f"executable cache: {self.compiles} compiles, {self.hits} hits "
            f"({self.exec_hit_rate:.0%} hit rate)",
            f"admission: {self.admitted} admitted, "
            f"{self.admit_deferrals} deferrals",
            f"decode: {self.segments} segments, {self.decode_steps} "
            f"slot-steps, wasted_step_frac {self.wasted_step_frac:.2f}",
        ]
        if self.pool_blocks:
            lines.append(
                f"kv pool: {self.pool_in_use}/{self.pool_blocks} blocks "
                f"(peak {self.pool_in_use_peak}), "
                f"prefix hit rate {self.prefix_hit_rate:.0%} "
                f"({self.prefix_block_hits}/{self.prefix_prompt_blocks} "
                f"blocks, {self.chunk_interior_hits} interior), "
                f"{self.stage_chunks} staged chunks, "
                f"{self.stage_stalls} stalls, {self.cow_copies} COW, "
                f"{self.evictions} evictions",
            )
        if self.spec_steps:
            lines.append(
                f"speculative: {self.spec_steps} steps, "
                f"{self.spec_accepted}/{self.spec_drafted} drafts accepted "
                f"({self.spec_acceptance_rate:.0%}), "
                f"{self.spec_commit_copies} commit copies",
            )
        if self.retrievals:
            lines.append(
                f"retrieval: {self.retrievals} queries "
                f"({self.retrieval_overlap_frac:.0%} overlapped), "
                f"chunk hit rate {self.retrieval_chunk_hit_rate:.0%} "
                f"({self.retrieval_chunk_hits}/"
                f"{self.retrieval_chunk_blocks} blocks)",
            )
        if (self.preemptions or self.restores or self.cancelled
                or self.watchdog_events):
            lines.append(
                f"robustness: {self.preemptions} preemptions "
                f"({self.spilled_blocks} blocks spilled), "
                f"{self.restores} restores, {self.unstaged} unstaged, "
                f"{self.cancelled} cancelled, "
                f"{self.watchdog_events} watchdog events",
            )
        return "\n".join(lines)


class ContinuousBatchingServer:
    """Slot-based continuous batching with batched segment decode.

    >>> srv = ContinuousBatchingServer(cfg, params, num_slots=4)
    >>> srv.submit([1, 2, 3], max_new_tokens=16)
    >>> srv.submit([4, 5], 16, sample=SamplingParams(temperature=0.8))
    >>> done = srv.run()          # drain pending + active
    """

    def __init__(self, cfg: ModelConfig, params, *, mesh=None,
                 num_slots: int = 4, max_len: int = 256,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 segment: int = 8, admit_batch: int = 2,
                 scheduling: str = "edf",
                 faults: FaultInjector | None = None,
                 plan: LayerPlan | ExecutionPlan | ExecutionMode | str |
                 None = None) -> None:
        if scheduling not in ("edf", "fifo"):
            raise ValueError(
                f"scheduling must be 'edf' or 'fifo', got {scheduling!r}")
        if cfg.family not in _SUPPORTED_FAMILIES:
            raise ValueError(
                f"continuous batching supports families {_SUPPORTED_FAMILIES}"
                f", got {cfg.family!r} (encoder-memory families need "
                "per-request memory plumbing — see module docstring)"
            )
        if plan is None:
            plan = ExecutionMode.SIDEBAR
        if isinstance(plan, ExecutionPlan):
            if not plan.is_uniform:
                cfg = dataclasses.replace(cfg, scan_layers=False)
            self._plan_key: Any = plan.cache_key()
        else:
            plan = coerce_layer_plan(plan)
            self._plan_key = plan
        self.cfg = cfg
        self.params = params
        self.plan = plan
        self.mesh = mesh
        self.api = get_model(cfg)
        # mesh => tensor-parallel serving: every step program below runs
        # under shard_map with params/pool partitioned on "model"
        self.tp = make_tp_spec(cfg, self.api, mesh) if mesh is not None \
            else None
        self.minfo = self.tp.minfo if self.tp is not None else L.HOST
        # folded into EVERY executable-cache key by _compiled: a server
        # on a different mesh (or none) can never reuse a stale program
        self._mesh_key = self.tp.mesh_key if self.tp is not None else None
        if self.tp is not None:
            self.params = self.tp.place_params(params)
        if not self.api.rowwise_decode_pos:
            raise ValueError(
                f"family {cfg.family!r} decode_step takes scalar positions "
                "only; batched segment decode needs per-row (B,) positions "
                "(ModelApi.rowwise_decode_pos)"
            )
        self.num_slots = num_slots
        self.max_len = max_len
        # a bucket longer than the KV cache could never be prefilled into
        # it; submit() bounds every prompt to max_len, so exact-fit covers
        # whatever the dropped buckets would have
        self.buckets = tuple(sorted(b for b in buckets if b <= max_len))
        self.segment = segment
        self.admit_batch = max(1, min(admit_batch, num_slots))
        self.slots = [_Slot() for _ in range(num_slots)]
        self.pending: collections.deque = collections.deque()
        self.finished: list[FinishedRequest] = []
        self._next_rid = 0
        self._exec: dict[tuple, Callable] = {}
        # the running token of every slot, device-side (N, 1): written
        # ONLY by program outputs (segment final carry / admission
        # correction scatter), so the drain loop never blocks on it
        self._toks = jnp.zeros((num_slots, 1), jnp.int32)
        self._done_raw: list[tuple] = []   # retired, not yet materialized
        self._deferred = False             # admission hysteresis armed
        self.stats = SchedulerStats()
        # host spans while a profiler session collects (launch.tracing)
        self.tracer = Tracer()
        self._uncalled: set = set()        # built by _compiled, not yet run
        # SLO scheduling: "edf" admits/stages by (priority, deadline,
        # arrival); "fifo" is the strict-arrival baseline the overload
        # bench compares against (preemption still guards lazy growth)
        self.scheduling = scheduling
        self.faults = faults
        self._seq = 0                      # arrival index (score tie-break)
        self._clock = time.monotonic       # injectable for deterministic tests
        self._timer = time.perf_counter    # injectable (watchdog timing)
        # segment watchdog: a dispatch past k * median segment wall is a
        # recorded (non-fatal) event — a wedged compile or device hang
        # becomes observable instead of silent
        self.watchdog = SegmentWatchdog()
        self._init_kv()

    def _init_kv(self) -> None:
        """Allocate the KV memory (hook: the paged subclass builds a
        block pool here instead of the dense slab)."""
        self.axes = probe_batch_axes(self.api, self.cfg, self.minfo,
                                     self.max_len)
        # THE slot cache: allocated once, lives as long as the server.
        self.cache = self.api.init_cache(self.cfg, self.minfo,
                                         self.num_slots, self.max_len)
        if self.tp is not None:
            # KV heads live on the model axis; everything else replicates
            self.cache = self.tp.place_cache(self.cache)

    # -- executable cache --------------------------------------------------
    def _compiled(self, key: tuple, builder: Callable[[], Callable]):
        """(kind, shape-key..., plan, mesh) -> compiled program. Repeat
        traffic hits the cache; a new bucket or plan is a recorded
        compile. The (mesh shape, axis names) tail means a server
        rebuilt on a different mesh can never replay a program whose
        shard_map was specialized to another device grid."""
        key = key + (self._mesh_key,)
        fn = self._exec.get(key)
        if fn is None:
            fn = self._exec[key] = builder()
            self._uncalled.add(fn)
            self.stats.compiles += 1
        else:
            self.stats.hits += 1
        return fn

    def _launch(self, fn: Callable):
        """The span around a call of ``fn``: ``compile`` on the first
        call of a program ``_compiled`` built (it traces, lowers and
        compiles the program, or loads it from the persistent cache),
        ``dispatch`` on every later one."""
        if fn in self._uncalled:
            self._uncalled.discard(fn)
            self.stats.compile_calls += 1
            return self.tracer.span("compile")
        return self.tracer.span("dispatch")

    def executable_cache_keys(self) -> list[tuple]:
        return sorted(self._exec, key=repr)

    # -- submission --------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n (prefill length); exact fit past the end."""
        for b in self.buckets:
            if b >= n:
                return b
        return n

    def submit(self, prompt, max_new_tokens: int,
               sample: SamplingParams | None = None, *,
               priority: int = 0, ttft_target: float | None = None,
               itl_target: float | None = None) -> int:
        """Enqueue a request; returns its rid (echoed on the
        ``FinishedRequest``). ``sample=None`` decodes greedy; a
        ``SamplingParams`` gives the request its own temperature/
        truncation/seed (the position-keyed PRNG makes the stream
        independent of batching and scheduling). ``priority`` ranks
        requests for staging/admission (higher first under
        ``scheduling="edf"``; ignored by FIFO), and ``ttft_target`` /
        ``itl_target`` (seconds) attach SLO targets: the TTFT target
        sets the EDF deadline (``submit time + target``); each finished
        request reports its own ``r.ttft`` / ``r.itl`` (dispatch
        times). No-target requests are best-effort
        — they sort behind every deadline but are never starved of a
        free slot."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt {prompt.size} + max_new {max_new_tokens} exceeds "
                f"max_len {self.max_len}"
            )
        rid = self._next_rid
        self._next_rid += 1
        self.pending.append(_Request(
            rid, prompt, max_new_tokens, sample,
            priority=int(priority), ttft_target=ttft_target,
            itl_target=itl_target, submit_t=self._clock(), seq=self._seq,
        ))
        self._seq += 1
        return rid

    def _score(self, req: _Request) -> tuple:
        """Scheduling order, smaller = sooner. EDF: priority class first
        (higher wins), earliest deadline inside a class, arrival as the
        strict tie-break; no-target requests (deadline inf) are
        best-effort behind every deadline. FIFO: arrival only — the
        overload bench's baseline."""
        if self.scheduling == "fifo":
            return (req.seq,)
        return (-req.priority, req.deadline, req.seq)

    def cancel(self, rid: int) -> bool:
        """Client abort: drop the request wherever it lives. A pending
        request vanishes; an active one frees its slot (the paged
        subclass releases its pool blocks — refcounts back, COW parents
        intact) at the current boundary. Cancelled requests never
        appear in ``finished``; sibling rows are untouched (their KV
        lives in other slots/blocks). Returns False for unknown/already
        finished rids."""
        for req in self.pending:
            if req.rid == rid:
                self.pending.remove(req)
                self.stats.cancelled += 1
                return True
        for i, slot in enumerate(self.slots):
            if slot.rid == rid:
                self._free_slot(i)
                self.stats.cancelled += 1
                return True
        return False

    # -- admission ---------------------------------------------------------
    def _admit_fn(self, *, with_prefill: bool) -> Callable:
        """ONE compiled program for a whole admission round, in place on
        the slot cache: gather the freed rows (probed batch axes),
        right-padded batched prefill of every co-admitted ``prompt[:-1]``
        (skipped when all prompts are single tokens), the per-row-
        position correction step, and the scatter back. The gathered
        rows still hold retired requests' KV — stale state is
        overwritten by the prefill/decode writes or masked by the causal
        ``kpos <= pos`` read before it is ever visible (the same
        argument as prompt bucketing)."""
        prefill_step = make_prefill_step(self.cfg, self.api, self.minfo,
                                         self.mesh, tp=self.tp)
        serve_step = make_serve_step(self.cfg, self.api, self.minfo,
                                     self.mesh, tp=self.tp)
        axes = self.axes

        def admit(params, padded, full, prev_toks, toks, pos, slots,
                  sample=None):
            rows = jax.tree.map(
                lambda f, ax: jnp.take(f, slots, axis=ax), full, axes)
            if with_prefill:
                _, rows = prefill_step(params, {"tokens": padded}, rows)
            nxt, rows = serve_step(params, toks, rows, pos, None, sample)
            # single-advanced-index scatter: the axis keeps its position
            full = jax.tree.map(
                lambda f, o, ax: f.at[(slice(None),) * ax + (slots,)].set(
                    o.astype(f.dtype)),
                full, rows, axes,
            )
            # merge the correction tokens into the running (N, 1) token
            # vector so the next segment feeds them without a host sync
            prev_toks = prev_toks.at[slots].set(nxt)
            return nxt, prev_toks, full

        return jax.jit(admit, donate_argnums=(2, 3))

    def _admit_batch(self, slot_idxs: list[int],
                     reqs: list[_Request]) -> None:
        """Admit ``k`` requests in ONE dispatch: gather the freed slot
        rows, right-padded batched prefill (to the largest needed
        bucket), the correction step at per-row true positions (the same
        rowwise-position machinery as segment decode), and the scatter
        back — all fused into one compiled program per admission ROUND
        instead of three dispatches per request.

        Padding is still invisible in tokens: each row's pad KV beyond
        its true length is overwritten by the correction step / later
        decode writes or masked by the causal ``kpos <= pos`` attention
        mask before it is ever read. (MoE caveat: co-admitted rows share
        expert capacity in the batched prefill — as with bucket padding,
        serve MoE with a no-drop capacity factor for bit-parity.)
        """
        k = len(reqs)
        s_true = np.asarray([r.prompt.size for r in reqs], np.int32)
        need = int(s_true.max()) - 1
        bucket = self.bucket_for(need) if need > 0 else 0
        padded = None
        if bucket:
            buf = np.zeros((k, bucket), np.int32)
            for j, r in enumerate(reqs):
                buf[j, : r.prompt.size - 1] = r.prompt[:-1]
            padded = jnp.asarray(buf)
        # prefill + correction fused into ONE program: each row's true
        # last prompt token decodes at its true per-row position,
        # overwriting the first pad's KV and yielding the first new token
        # from the right logits row. A sampled request samples it with
        # key (base, s_true) — exactly the key a solo Server.generate
        # folds for its first new token.
        keys = [None if r.sample is None else np.asarray(
            sampling.request_key(r.sample.seed)) for r in reqs]
        sampled = any(r.sample is not None for r in reqs)
        zero = np.zeros((2,), np.uint32)
        state = sampling.merge_rows(
            [(zero if key is None else key, r.sample)
             for key, r in zip(keys, reqs)]) if sampled else None
        admit = self._compiled(
            ("prefill", k, bucket, self._plan_key,
             "sampled" if sampled else "greedy"),
            lambda: self._admit_fn(with_prefill=bool(bucket)),
        )
        toks = np.asarray([[r.prompt[-1]] for r in reqs], np.int32)
        with self._launch(admit):
            nxt, self._toks, self.cache = admit(
                self.params, padded, self.cache, self._toks,
                jnp.asarray(toks), jnp.asarray(s_true - 1),
                jnp.asarray(slot_idxs, jnp.int32), state,
            )
        now = self._clock()
        for j, slot_idx in enumerate(slot_idxs):
            r = reqs[j]
            slot = self.slots[slot_idx]
            slot.rid = r.rid
            slot.pos = int(s_true[j])
            slot.remaining = r.max_new - 1
            slot.generated = 1
            slot.chunks = [(nxt, j, 1)]
            slot.prompt = r.prompt
            slot.sample = r.sample
            slot.key = keys[j]
            slot.req = r
            slot.first_t = now     # first token dispatched here
            self.stats.admitted += 1
            if slot.remaining == 0:
                self._retire(slot_idx)

    def _free_slot(self, slot_idx: int) -> None:
        """Vacate a slot without retiring it (cancel path; the paged
        subclass also releases the request's pool blocks)."""
        self.slots[slot_idx] = _Slot()

    def _retire(self, slot_idx: int) -> None:
        slot = self.slots[slot_idx]
        ttft = itl = float("nan")
        if slot.req is not None and slot.first_t is not None:
            ttft = slot.first_t - slot.req.submit_t
            if slot.generated > 1:
                itl = (self._clock() - slot.first_t) / (slot.generated - 1)
        self._done_raw.append((slot.rid, slot.prompt, slot.chunks,
                               slot.generated, ttft, itl))
        self._free_slot(slot_idx)

    @staticmethod
    def _chunks_to_np(chunks: list[tuple], fetched: dict) -> np.ndarray:
        """Host tokens from (device_array, row, take) handles — the one
        place the async pipeline blocks. ``fetched`` memoizes whole-
        array transfers (many chunks share one segment buffer)."""
        if not chunks:
            # a request preempted before its first token has no chunks
            return np.zeros((0,), np.int32)
        parts = []
        for arr, row, take in chunks:
            host = fetched.get(id(arr))
            if host is None:
                host = fetched[id(arr)] = np.asarray(arr)
            parts.append(host[row, :take])
        return np.concatenate(parts).astype(np.int32)

    def slot_tokens(self, slot_idx: int) -> np.ndarray:
        """Tokens generated so far by the request in ``slot_idx`` (syncs
        that slot's chunks; used for mid-stream inspection/restart)."""
        return self._chunks_to_np(self.slots[slot_idx].chunks, {})

    def _materialize(self) -> list[FinishedRequest]:
        """Convert retired-but-raw requests into FinishedRequests."""
        if not self._done_raw:
            return []
        if any(isinstance(arr, jax.Array) and not arr.is_ready()
               for _, _, chunks, *_ in self._done_raw
               for arr, _, _ in chunks):
            self.stats.retire_waits += 1
        fetched: dict = {}
        out = []
        for rid, prompt, chunks, generated, ttft, itl in self._done_raw:
            tokens = self._chunks_to_np(chunks, fetched)
            assert tokens.size == generated
            out.append(FinishedRequest(
                rid=rid, prompt=prompt, tokens=tokens,
                prompt_len=int(prompt.size), generated=generated,
                ttft=ttft, itl=itl,
            ))
        self._done_raw.clear()
        self.finished.extend(out)
        return out

    def admit(self) -> int:
        """Fill free slots from the pending queue (one batched admission
        round); returns #admitted.

        Admission hysteresis: with a backlog and other slots still
        decoding, wait until ``admit_batch`` slots are free before
        admitting — a batch-1 prefill GEMM is several times less
        efficient than a batched one, and a short wait for a second free
        slot costs less than it saves (knob: ``admit_batch=1`` restores
        eager admission). The wait times out after ONE deferred
        boundary: ``_segment_steps`` caps the next segment at
        ``self.segment`` while a deferral is pending, and the boundary
        after that admits whatever is free — a held-open slot never
        idles longer than ``segment`` steps behind a long-running
        neighbour.
        """
        free = [i for i, slot in enumerate(self.slots) if slot.free]
        take = min(len(free), len(self.pending))
        if take == 0:
            self._deferred = False
            return 0
        threshold = min(self.admit_batch, len(self.pending))
        if (take < threshold and len(free) < self.num_slots
                and not self._deferred):
            self._deferred = True
            self.stats.admit_deferrals += 1
            return 0
        self._deferred = False
        # out-of-order admission: the best-scored pending requests go
        # first (EDF inside priority classes); default traffic (no
        # priorities, no deadlines) scores by arrival — exactly FIFO
        reqs = sorted(self.pending, key=self._score)[:take]
        for r in reqs:
            self.pending.remove(r)
        with kops.execution_plan(self.plan):
            self._admit_batch(free[:take], reqs)
        return take

    # -- segment decode ----------------------------------------------------
    def _segment_fn(self, num_steps: int) -> Callable:
        """All slots advance ``num_steps`` tokens in one compiled program:
        ONE batched ``make_serve_step`` over the whole slot cache,
        scanned over steps with the cache in the (donated) carry and the
        output buffer written via ``dynamic_update_slice``. ``pos`` is a
        per-row ``(N,)`` vector (unaligned slots: the attention layer
        scatters each row's KV write to its own position) or a scalar
        (every slot at the same position: dense-slab KV writes, the same
        program shape as ``serve.make_decode_scan``). Either way the
        matmuls stay dense over slots — no per-slot vmap into batch-1
        programs.
        """
        step = make_serve_step(self.cfg, self.api, self.minfo, self.mesh,
                               tp=self.tp)
        max_pos = self.max_len - 1

        def segment(params, toks, cache, pos, sample=None):
            # toks (N, 1); pos (N,) or scalar; cache = the full slot
            # cache. Finished/free slots idle at a clamped position:
            # their writes land on a dead row and are overwritten
            # wholesale at the next admission.
            buf = jnp.zeros((toks.shape[0], num_steps), jnp.int32)

            def body(carry, i):
                tok, cache, buf = carry
                p = jnp.minimum(pos + i, max_pos)
                nxt, cache = step(params, tok, cache, p, None, sample)
                buf = jax.lax.dynamic_update_slice(buf, nxt, (0, i))
                return (nxt, cache, buf), None

            (last, cache, buf), _ = jax.lax.scan(
                body, (toks, cache, buf),
                jnp.arange(num_steps, dtype=jnp.int32),
            )
            # the final carry token feeds the next segment directly —
            # the drain loop never syncs token values (async dispatch)
            return buf, last, cache

        # params as an ARGUMENT (not a closure constant): the cached
        # executable never bakes weights into its jaxpr, and a params
        # swap on a live server takes effect on the next segment.
        return jax.jit(segment, donate_argnums=(2,))

    def _segment_sample_state(self, active: list[int]) -> dict | None:
        """Per-row traced sampling state for one segment, or ``None``
        when every active slot decodes greedily (keeps the pure-greedy
        segment program free of sampling math). Greedy slots inside a
        mixed batch ride along as temperature-0 rows — exact argmax."""
        if not any(self.slots[i].sample is not None for i in active):
            return None
        zero = np.zeros((2,), np.uint32)
        rows = []
        for slot in self.slots:
            if slot.free or slot.sample is None:
                rows.append((zero, None))
            else:
                rows.append((slot.key, slot.sample))
        return sampling.merge_rows(rows)

    def _segment_steps(self, active: list[int], *,
                       draining: bool = False) -> int:
        """How many tokens this segment decodes — shrink-to-fit.

        The segment ends exactly when the earliest active slot finishes
        (``min remaining``): running past it wastes slot-steps, and with
        EVERY slot busy a boundary before it is pure dispatch overhead —
        admission needs a free slot, and only a retirement frees one, so
        nothing can enter earlier (holds for live submits too). Whenever
        entry IS possible at the boundary — a free slot exists and a
        live submit could arrive (``step()``-driven serving; inside a
        blocking ``run()`` drain nothing can be submitted, so the cap
        would be pure dispatch overhead on the tail) or an admission
        deferral is armed (the hysteresis must time out) — the length is
        capped at ``self.segment``, the admission-latency knob. Above
        ``self.segment`` the length rounds down to a power of two, so
        long stretches cost O(log) dispatches while the distinct
        compiled segment variants stay bounded (``segment`` exact
        lengths + log2(max_len) strides).
        """
        min_rem = min(self.slots[i].remaining for i in active)
        entry_possible = self._deferred or (
            not draining and any(s.free for s in self.slots))
        if entry_possible:
            return min(min_rem, self.segment)
        if min_rem <= self.segment:
            return min_rem
        return 1 << (min_rem.bit_length() - 1)

    def _advance(self, *, draining: bool = False) -> None:
        """One scheduler iteration, fully async: admit into free slots,
        then enqueue one segment over all active slots. All decisions
        (segment length, alignment, retirement) derive from host-side
        COUNTS; token values stay on device — the admission program
        merges its correction tokens into the running token vector and
        the segment program returns its final carry, so dispatches
        pipeline without a single host round-trip. ``draining`` marks a
        blocking ``run()`` loop, where no live submit can arrive."""
        self.admit()
        active = [i for i, s in enumerate(self.slots)
                  if not s.free and s.remaining > 0]
        if not active:
            return
        steps = self._segment_steps(active, draining=draining)
        pos = np.full((self.num_slots,), self.max_len - 1, np.int32)
        for i in active:
            pos[i] = self.slots[i].pos
        # aligned fast path: every slot occupied at the same position
        # -> scalar-pos program (dense dynamic_update_slice KV writes)
        aligned = (len(active) == self.num_slots
                   and len({self.slots[i].pos for i in active}) == 1)
        state = self._segment_sample_state(active)
        seg = self._compiled(
            ("segment", self.num_slots, steps,
             "aligned" if aligned else "ragged",
             "sampled" if state is not None else "greedy",
             self._plan_key),
            lambda: self._segment_fn(steps),
        )
        pos_arg = (jnp.int32(self.slots[active[0]].pos) if aligned
                   else jnp.asarray(pos))
        t0 = self._timer()
        with self._launch(seg), kops.execution_plan(self.plan):
            buf, self._toks, self.cache = seg(
                self.params, self._toks, self.cache, pos_arg, state)
        # segment dispatch wall (trace + enqueue; execution is async) —
        # a wedged compile shows up here, and on the host backends the
        # dispatch is effectively synchronous so hangs do too
        if self.watchdog.observe(self._timer() - t0):
            self.stats.watchdog_events += 1
        self.stats.segments += 1
        self.stats.decode_steps += steps * len(active)
        # shrink-to-fit guarantees steps <= every active slot's remaining
        # (no active slot overshoots); the waste that remains is the
        # free/dead rows the batched program decodes alongside them
        self.stats.wasted_steps += steps * (self.num_slots - len(active))
        for i in active:
            slot = self.slots[i]
            take = min(steps, slot.remaining)
            slot.chunks.append((buf, i, take))
            slot.generated += take
            slot.remaining -= take
            slot.pos += take
            if slot.remaining == 0:
                self._retire(i)

    def step(self, *, draining: bool = False) -> list[FinishedRequest]:
        """Admit into free slots, then decode one segment on all active
        slots; returns requests that finished this step (synced).
        ``draining=True`` tells segment sizing no live submit can arrive
        (the router's step-wise drain uses it to keep boundaries
        identical to a blocking ``run()``). Under a profiler session the
        call is a ``step`` span, the host copy of finished tokens a
        ``retire`` span inside it (see ``launch.tracing``)."""
        with self.tracer.span("step"):
            self._advance(draining=draining)
            with self.tracer.span("retire"):
                return self._materialize()

    def _has_work(self) -> bool:
        return bool(self.pending) or any(not s.free for s in self.slots)

    @property
    def load(self) -> int:
        """Outstanding requests on this server: queued + occupying a
        slot. The replica router's least-loaded signal."""
        return len(self.pending) + sum(not s.free for s in self.slots)

    def run(self) -> list[FinishedRequest]:
        """Drain every pending + active request; returns all finished
        requests (ordered by rid). The whole drain is enqueued without
        host syncs; tokens are fetched once at the end."""
        while self._has_work():
            self._advance(draining=True)
        self._materialize()
        out, self.finished = self.finished, []
        return sorted(out, key=lambda r: r.rid)


# ---------------------------------------------------------------------------
# Paged KV pool scheduler: block tables + prefix caching + prefill-ahead.
# ---------------------------------------------------------------------------


_rag_io_pool: Any = None


def _rag_io():
    """The shared single-thread retrieval worker. ONE worker on
    purpose: queries retrieve strictly in submission order, and
    ``RagPipeline.retrieve`` is a pure function of the query over a
    read-only index, so backgrounding it cannot reorder or change any
    result — only move its wall time off the dispatch thread (where
    sleeps in a modeled payload fetch and numpy BLAS both release the
    GIL and genuinely overlap the synchronous segment dispatch)."""
    global _rag_io_pool
    if _rag_io_pool is None:
        import concurrent.futures
        _rag_io_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="rag-io")
    return _rag_io_pool


@dataclasses.dataclass(eq=False)
class _PendingQuery:
    """A RAG query waiting for its retrieval turn: everything a
    ``_Request`` needs except the prompt, which retrieval + assembly
    produce. ``seq`` is reserved at submit, so a query's scheduling
    score is its ARRIVAL order — retrieval latency never reorders it
    behind later plain submits."""

    rid: int
    query: np.ndarray
    max_new: int
    sample: SamplingParams | None
    priority: int
    ttft_target: float | None
    itl_target: float | None
    submit_t: float
    seq: int


@dataclasses.dataclass(eq=False)
class _Spilled:
    """A preempted request waiting to resume: its generated tokens are
    synced to host numpy and its KV block payload parked in the
    ``SidebarSpillRegion`` (keyed by rid). Holds ZERO pool blocks — a
    spilled request can never pin memory or block an eviction."""

    req: _Request
    generated: int
    tokens: np.ndarray        # (generated,) int32
    valid_end: int            # KV valid on [0, valid_end) at restore
    n_blocks: int             # payload blocks (stats/bookkeeping)
    first_t: float | None     # original first-token time (TTFT keeps it)


def _hole_spans(hit_idx: tuple[int, ...], target: int,
                block_size: int) -> list[list[int]]:
    """Position spans ``[start, end)`` of ``[0, target)`` NOT covered by
    spliced hit blocks — what staging must still prefill. Contiguous
    misses merge into one span; with no interior hits this degenerates
    to the classic single ``[hit_len, target)`` frontier."""
    spans: list[list[int]] = []
    hit = set(hit_idx)
    p = 0
    while p < target:
        j = p // block_size
        if j in hit:
            p = (j + 1) * block_size
            continue
        e = min((j + 1) * block_size, target)
        if spans and spans[-1][1] == p:
            spans[-1][1] = e
        else:
            spans.append([p, e])
        p = e
    return spans


@dataclasses.dataclass(eq=False)
class _Staging:
    """A request whose prompt KV is being staged block-by-block into
    the pool (chunked prefill-ahead), before it owns any slot — or a
    restored spill (``resume`` set) that re-enters through the same
    staged -> admitted path with its KV already in place.

    ``todo`` holds the position spans still needing prefill, in order.
    Interior-hole splicing makes hits sparse, so this is a span LIST,
    not a single frontier: hit blocks between spans already hold valid
    KV and are never written. Spans complete front to back (a later
    span's prefill attends to everything before it, so the earlier
    span's KV must land first)."""

    req: _Request
    rb: kvp.RequestBlocks
    todo: list[list[int]]     # [start, end) spans, ascending, disjoint
    resume: _Spilled | None = None

    @property
    def done(self) -> bool:
        return not self.todo


class PagedContinuousBatchingServer(ContinuousBatchingServer):
    """Continuous batching over a block-granular paged KV pool.

    Same external contract as the slab scheduler (``submit`` / ``step``
    / ``run``, bit-identical tokens), different memory and admission
    disciplines:

      * **Paged KV** — ONE physical block pool (``launch.kvpool``)
        instead of per-slot max-length rows; each request maps its
        positions onto pooled blocks through a logical block table.
        With the default ``kernel="paged"`` the segment program decodes
        IN PLACE on the pool: per-step writes land through the tables
        and attention walks them directly
        (``kernels.ops.paged_attention_*``), no pool-wide copies.
        ``kernel="slab"`` keeps the original gather → dense decode →
        scatter segment as the reference implementation. Capacity is
        ``num_blocks * block_size`` *positions*, shared: short requests
        no longer reserve max_len rows.
      * **Prefix caching** — full prompt blocks are hash-consed: a
        request whose prompt starts with an already-served prefix
        splices those blocks (refcount bump) instead of recomputing
        their KV; retired requests' published blocks stay cached until
        LRU eviction. Copy-on-write isolates any write into shared
        state (structurally unreachable today — sharing stops before
        every write range — but enforced, not assumed).
      * **Chunked prefill-ahead** — pending requests' prompt KV stages
        in fixed-size chunks BETWEEN decode segments (one bounded
        staging program per boundary while slots decode), so by the
        time a slot frees, admission is a host-side block-table splice.
        The correction step — decode of the true last prompt token at
        its true position — is the admitted row's FIRST step of the
        very next segment program: admission costs zero extra
        dispatches, closing the admission/segment-fusion open item (one
        program per scheduler iteration, vs prefill + correction +
        segment at the slab scheduler's boundary).
      * **Lazy allocation + preemption** — ``begin_request`` reserves
        the staged span only; ``_grow_active`` takes decode blocks as
        each span crosses a block boundary, so a small pool
        oversubscribes until it genuinely can't. When a higher-scored
        arrival (see ``_score``: priority class, then EDF deadline)
        cannot stage, the scheduler reclaims from strictly worse-scored
        holders — unstage first, then spill the worst active span's KV
        to the host-side ``SidebarSpillRegion`` and hand over its slot.
        Restore splices the blocks back and resumes position-exact; a
        preempted-then-restored drain is token-identical to an
        unpressured one (the position-keyed PRNG never sees scheduling
        history).
      * **Speculative decoding** — with ``spec=SpecConfig(...)`` each
        segment step becomes draft → verify → commit: the draft model
        proposes ``spec.k`` tokens per row from its own dense slot
        cache (never the pool), the target verifies all k+1 positions
        in ONE batched rowwise prefill through the block tables, and
        the host commits the accepted prefix (+ the target's own token
        at the first mismatch) by lazy span growth + scratch→pool
        block copies. Verify KV for not-yet-granted positions lands in
        per-slot spare scratch blocks outside the allocator, so a
        rejected draft allocates nothing; a fully-rejected step still
        emits one token. Emitted tokens are bit-identical to plain
        decode for any draft, greedy and sampled.

    Numerics: the table-ordered (B, nb*block_size) view — gathered by
    the slab segment, walked in place by the paged kernel — equals the
    slab cache wherever the causal mask looks (junk in unwritten blocks
    sits behind ``kpos <= pos`` exactly like a slab's stale tail), and
    masked logits at -1e30 underflow to exactly 0.0 in f32, so slicing
    the table to the active frontier changes no sum — generation is
    bit-identical to the slab scheduler AND to solo decode, prefix hits
    and chunk boundaries included. (MoE under a
    dropping capacity factor: chunk boundaries change which tokens
    compete, the same caveat as prompt bucketing — serve no-drop for
    bit-parity.) Sampling needs nothing new: the position-keyed PRNG
    never sees block geometry.
    """

    def __init__(self, cfg: ModelConfig, params, *,
                 block_size: int = 16, num_blocks: int | None = None,
                 prefill_chunk: int | None = None,
                 stage_ahead: int | None = None,
                 spill_region: SidebarSpillRegion | None = None,
                 kernel: str = "paged",
                 spec: SpecConfig | None = None,
                 rag=None, rag_overlap: bool = True, **kw) -> None:
        if kernel not in ("paged", "slab"):
            raise ValueError(
                f"kernel must be 'paged' or 'slab', got {kernel!r}"
            )
        # speculative decoding (launch.spec): replaces segment decode
        # with draft -> one-program verify -> host-side accept/commit.
        # spec.k == 0 (or None) keeps plain segment decode, bit-exactly.
        self.spec = spec
        self._spec_on = spec is not None and spec.k > 0
        if self._spec_on:
            spec.validate(cfg)
        # ``kernel="paged"`` (default): segment decode runs IN PLACE on
        # the block pool through ``kernels.ops.paged_attention_*`` —
        # zero pool-wide gather/scatter copies, tables sliced to the
        # active frontier. ``kernel="slab"`` keeps the dense round-trip
        # segment (gather_blocks / scatter_blocks) as the reference.
        self.kernel = kernel
        # consumed by _init_kv, which super().__init__ calls
        self.block_size = int(block_size)
        self._num_blocks_arg = num_blocks
        self.prefill_chunk = int(prefill_chunk or block_size)
        self._stage_ahead_arg = stage_ahead
        self._spill_region_arg = spill_region
        # retrieval stage (``rag=RagPipeline(...)``): ``submit_query``
        # parks queries here. With ``rag_overlap`` (default) the search
        # itself — the expensive, I/O-shaped half — starts immediately
        # on a background worker (``_rag_io``), so it runs concurrently
        # with whatever the scheduler does next, including the segment
        # dispatch (which on the CPU backend blocks for the whole
        # segment: donated cache buffers make dispatch synchronous, so
        # single-threaded retrieve-after-dispatch would hide nothing).
        # ``_drain_queries`` then collects the ranked result and does
        # the cheap assembly + staging at the boundary AFTER the
        # dispatch — retrieval for request N+1 hidden behind the
        # accelerator decoding active requests, the sidebar overlap
        # schedule at serving granularity. ``rag_overlap=False`` never
        # kicks off the worker: it quiesces in-flight device work and
        # retrieves serially before staging — the retrieve-then-decode
        # pipeline, the bench's comparison arm.
        self.rag = rag
        if rag is not None and rag.block_size != int(block_size):
            raise ValueError(
                f"RagPipeline block_size {rag.block_size} != scheduler "
                f"block_size {block_size}: chunk boundaries must land on "
                "pool block boundaries"
            )
        self.rag_overlap = bool(rag_overlap)
        self._queries: collections.deque[_PendingQuery] = (
            collections.deque())
        self._rag_futures: dict[int, Any] = {}      # rid -> Future
        self._rag_meta: dict[int, list[int]] = {}   # rid -> chunk blocks
        self.rag_results: dict[int, Any] = {}       # rid -> RagPrompt
        super().__init__(cfg, params, **kw)
        if self.faults is not None:
            # allocation-failure site: every alloc consults the injector
            self.mgr.alloc.fault_hook = (
                lambda: self.faults.fire("alloc"))

    def _init_kv(self) -> None:
        if self.max_len % self.block_size:
            raise ValueError(
                f"max_len {self.max_len} must be a multiple of "
                f"block_size {self.block_size} (tables are fixed-width; "
                "the gathered view must equal the slab shape)"
            )
        self.blocks_per_table = self.max_len // self.block_size
        nb = self._num_blocks_arg
        if nb is None:
            # full tables for every slot + staging/prefix slack + scratch
            nb = (self.num_slots + 2) * self.blocks_per_table + 1
        # speculative decoding: each slot owns a fixed private slice of
        # SPARE pool rows (outside the allocator — never refcounted,
        # never spilled) big enough for the worst-case drafted overhang:
        # k positions past a block-aligned frontier is ceil(k/bs) blocks.
        spec_k = self.spec.k if self._spec_on else 0
        self._n_scratch = -(-spec_k // self.block_size)
        self.mgr = kvp.PagedKVManager(
            self.api, self.cfg, self.minfo,
            num_blocks=nb, block_size=self.block_size,
            shardings=(self.tp.cache_shardings() if self.tp is not None
                       else None),
            spare_blocks=self.num_slots * self._n_scratch,
        )
        if self._spec_on:
            base = self.mgr.alloc.num_blocks
            self._scratch = [
                list(range(base + i * self._n_scratch,
                           base + (i + 1) * self._n_scratch))
                for i in range(self.num_slots)
            ]
            self.draft_api = self.spec.draft_api()
            self._draft_params = self.spec.draft_params
            # the draft's own dense slot cache — it NEVER takes pool
            # blocks; always unsharded (the draft is small by design)
            self._draft_cache = self.draft_api.init_cache(
                self.spec.draft_cfg, L.HOST, self.num_slots, self.max_len)
            # slot -> (rid, draft ingest frontier); keying by rid makes
            # slot reuse / spill / restore reset the frontier for free
            self._dpos: dict[int, tuple[int, int]] = {}
        self.cache = None  # the pool replaces the slab entirely
        self.stage_ahead = (self._stage_ahead_arg
                            if self._stage_ahead_arg is not None
                            else self.num_slots)
        # logical -> physical tables, host-side; unoccupied entries point
        # at the reserved scratch block (dead writes land in junk)
        self._tables = np.full((self.num_slots, self.blocks_per_table),
                               kvp.SCRATCH_BLOCK, np.int32)
        self._slot_rb: list[kvp.RequestBlocks | None] = (
            [None] * self.num_slots)
        self._staging: collections.deque[_Staging] = collections.deque()
        # preemption: spilled requests wait here; payloads live in the
        # host-side sidebar region keyed by rid
        # NOTE: explicit None test — an empty region is len() == 0,
        # i.e. falsy, and ``or`` would silently drop the caller's region
        self.spill = (self._spill_region_arg
                      if self._spill_region_arg is not None
                      else SidebarSpillRegion())
        self._spilled: list[_Spilled] = []
        # slot -> correction token for rows admitted this boundary (the
        # merge the segment program fuses); a dict so a victim spilled
        # between admission and dispatch just drops its entry
        self._admit_pending: dict[int, int] = {}
        self.stats.pool_blocks = self.mgr.alloc.capacity

    # -- bookkeeping -------------------------------------------------------
    def _sync_pool_stats(self) -> None:
        c = self.mgr.counters
        self.stats.cow_copies = c.cow_copies
        self.stats.evictions = c.evictions
        self.stats.prefix_block_lookups = c.prefix_block_lookups
        self.stats.prefix_block_hits = c.prefix_block_hits
        self.stats.prefix_prompt_blocks = c.prompt_blocks
        self.stats.chunk_interior_hits = c.chunk_interior_hits
        self.stats.pool_in_use = self.mgr.alloc.in_use
        self.stats.pool_in_use_peak = c.in_use_peak

    def _has_work(self) -> bool:
        return (super()._has_work() or bool(self._staging)
                or bool(self._spilled) or bool(self._queries))

    @property
    def load(self) -> int:
        return (super().load + len(self._staging) + len(self._spilled)
                + len(self._queries))

    def submit(self, prompt, max_new_tokens: int,
               sample: SamplingParams | None = None, *,
               priority: int = 0, ttft_target: float | None = None,
               itl_target: float | None = None) -> int:
        prompt_arr = np.asarray(prompt, np.int32).reshape(-1)
        if prompt_arr.size >= 1 and max_new_tokens >= 1:
            # allocation is lazy (the span grows segment by segment),
            # but the WORST-CASE span must fit the pool alone, or the
            # request could preempt everything and still wedge
            need = self.mgr.blocks_needed(
                prompt_arr.size + max_new_tokens - 1)
            if need > self.mgr.alloc.capacity:
                raise ValueError(
                    f"request needs {need} blocks, pool holds "
                    f"{self.mgr.alloc.capacity} — raise num_blocks or "
                    "shrink the request"
                )
        rid = super().submit(prompt, max_new_tokens, sample,
                             priority=priority, ttft_target=ttft_target,
                             itl_target=itl_target)
        # per request: queued until its first staging span is taken,
        # then staged until admission (launch.tracing)
        self.tracer.begin("queued", rid)
        return rid

    # -- retrieval stage (RAG) ---------------------------------------------
    def submit_query(self, query, max_new_tokens: int,
                     sample: SamplingParams | None = None, *,
                     priority: int = 0, ttft_target: float | None = None,
                     itl_target: float | None = None) -> int:
        """Enqueue a RAG query: retrieval + prompt assembly run later as
        host work between segment dispatches (``rag_overlap`` hides them
        behind the in-flight decode segment), then the assembled prompt
        enters the normal pending -> staging -> admission path. Returns
        the rid; the assembled ``RagPrompt`` (tokens + per-chunk
        provenance) lands in ``rag_results[rid]`` when retrieval runs.

        Validation is EAGER: the assembled length is deterministic
        before retrieval (system prefix + top_k chunks are fixed-size,
        the query rides verbatim), so a too-long or pool-overflowing
        request raises here, not mid-drain."""
        if self.rag is None:
            raise ValueError(
                "submit_query needs a RagPipeline: construct the server "
                "with rag=RagPipeline(...)")
        q = np.asarray(query, np.int32).reshape(-1)
        if q.size < 1:
            raise ValueError("empty query")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        s = self.rag.prompt_len_for + q.size
        if s + max_new_tokens > self.max_len:
            raise ValueError(
                f"assembled prompt {s} + max_new {max_new_tokens} "
                f"exceeds max_len {self.max_len}"
            )
        need = self.mgr.blocks_needed(s + max_new_tokens - 1)
        if need > self.mgr.alloc.capacity:
            raise ValueError(
                f"assembled request needs {need} blocks, pool holds "
                f"{self.mgr.alloc.capacity} — raise num_blocks or "
                "shrink the request"
            )
        rid = self._next_rid
        self._next_rid += 1
        self._queries.append(_PendingQuery(
            rid=rid, query=q, max_new=int(max_new_tokens), sample=sample,
            priority=int(priority), ttft_target=ttft_target,
            itl_target=itl_target, submit_t=self._clock(), seq=self._seq,
        ))
        self._seq += 1
        if self.rag_overlap:
            # start the search NOW on the I/O worker — it overlaps all
            # host work and dispatches until the drain collects it
            self._rag_futures[rid] = _rag_io().submit(
                self.rag.retrieve, q)
        return rid

    def _drain_queries(self, *, overlapped: bool) -> None:
        """Collect retrieval + run assembly for every parked query and
        promote it to a pending ``_Request``. Called at one of two
        points in the boundary: right AFTER a segment dispatch
        (``overlapped=True`` — the search has been running on the I/O
        worker since submit, hidden behind the dispatch; collecting it
        here costs only the uncovered remainder) or at the top of
        ``_advance`` when nothing is decoding / overlap is off (with
        overlap off there is no future and retrieval runs inline, on
        the critical path)."""
        while self._queries:
            pq = self._queries.popleft()
            fut = self._rag_futures.pop(pq.rid, None)
            rp = self.rag.assemble(
                pq.query, ranked=None if fut is None else fut.result())
            self.rag_results[pq.rid] = rp
            self._rag_meta[pq.rid] = rp.chunk_blocks(self.block_size)
            self.stats.retrievals += 1
            if overlapped:
                self.stats.retrieval_overlapped += 1
            self.pending.append(_Request(
                pq.rid, rp.tokens, pq.max_new, pq.sample,
                priority=pq.priority, ttft_target=pq.ttft_target,
                itl_target=pq.itl_target, submit_t=pq.submit_t,
                seq=pq.seq,
            ))

    def cancel(self, rid: int) -> bool:
        for pq in self._queries:
            if pq.rid == rid:
                self._queries.remove(pq)
                # an in-flight search is harmless (pure, read-only) —
                # just drop the handle so its result is never collected
                self._rag_futures.pop(rid, None)
                self.stats.cancelled += 1
                return True
        for st in self._staging:
            if st.req.rid == rid:
                # staged (or restored-but-unadmitted): release the
                # blocks; a cancelled request's KV needs no preserving
                self._staging.remove(st)
                self.mgr.release_request(st.rb)
                self.stats.cancelled += 1
                return True
        for sp in self._spilled:
            if sp.req.rid == rid:
                self._spilled.remove(sp)
                self.spill.release(rid)
                self.stats.cancelled += 1
                return True
        return super().cancel(rid)

    # -- chunked prefill-ahead (staging) -----------------------------------
    def _stage_fn(self) -> Callable:
        return jax.jit(
            make_prefill_step(self.cfg, self.api, self.minfo, self.mesh,
                              tp=self.tp),
            donate_argnums=(2,),
        )

    def _stage_round(self, entries: list[_Staging]) -> None:
        """ONE bounded staging program advances every incomplete staging
        entry by up to ``prefill_chunk`` tokens, each row writing at its
        own frontier through its own block table (the same rowwise-
        position machinery as ragged segment decode, at prefill width).
        Fixed chunk length + batch size keyed executables; the zero-
        padded tail of a final chunk writes junk beyond the prompt that
        later decode writes overwrite or the causal mask hides (the
        bucket-padding argument; MoE: padded/co-staged rows share expert
        capacity — serve no-drop for bit-parity, as with bucketing)."""
        k, c = len(entries), self.prefill_chunk
        bs = self.block_size
        toks = np.zeros((k, c), np.int32)
        pos = np.empty((k,), np.int32)
        bt = np.empty((k, self.blocks_per_table), np.int32)
        for j, st in enumerate(entries):
            s, e = st.todo[0]
            valid = min(e - s, c)
            toks[j, :valid] = st.req.prompt[s:s + valid]
            pos[j] = s
            row = np.asarray(st.rb.table_row(self.blocks_per_table)).copy()
            # the chunk's zero-padded tail writes junk past ``valid`` —
            # harmless when the following blocks are this request's own
            # fresh staged blocks (the classic case), fatal if one is a
            # SPLICED hit past an interior hole (junk would overwrite
            # live shared KV). Divert every block past the last validly
            # written one to the scratch row: junk lands in junk, and
            # no valid position in this chunk ever READS that far ahead
            # (causal attention looks backward only).
            row[(s + valid - 1) // bs + 1:] = kvp.SCRATCH_BLOCK
            bt[j] = row
        kvp.validate_tables(bt, self.mgr.pool.num_blocks)
        fn = self._compiled(
            ("stage", k, c, self.blocks_per_table, self._plan_key),
            self._stage_fn)
        with self._launch(fn), kops.execution_plan(self.plan):
            _, self.mgr.pool.cache = fn(
                self.params, {"tokens": jnp.asarray(toks)},
                self.mgr.pool.cache, None, jnp.asarray(pos),
                jnp.asarray(bt),
            )
        for st in entries:
            s, e = st.todo[0]
            if s + c >= e:
                st.todo.pop(0)
            else:
                st.todo[0][0] = s + c
        self.stats.stage_chunks += k

    def _stage(self, *, catch_up: bool) -> None:
        """Prefill-ahead: restore spilled requests into free slots'
        staging (they are furthest along), start staging the best-scored
        pending requests (prefix splice + staging-span allocation — the
        span is LAZY: only the prompt's blocks, growth comes per
        segment), then advance every incomplete staging entry by one
        batched chunk round — or to completion when there is no active
        decode to overlap behind (``catch_up``).

        Under pool pressure a better-scored request reclaims from
        strictly worse holders (``_reclaim_for``): a lower-priority
        staging entry is unstaged, an active slot preempted — this is
        how a high-priority arrival jumps a saturated replica."""
        self._try_restore()
        while self.pending:
            req = min(self.pending, key=self._score)
            if len(self._staging) >= self.stage_ahead:
                # staging entry slots are a resource too: a strictly
                # worse entry yields its place (EDF jump); FIFO scores
                # never reorder, so the baseline behaves as before
                worst = max(self._staging,
                            key=lambda st: self._score(st.req))
                if not self._score(req) < self._score(worst.req):
                    break
                self._unstage(worst)
            n_stage = max(int(req.prompt.size) - 1, 1)
            rb = self.mgr.begin_request(req.prompt, n_stage)
            while rb is None and self._reclaim_for(self._score(req)):
                rb = self.mgr.begin_request(req.prompt, n_stage)
            if rb is None:
                self.stats.stage_stalls += 1
                break
            self.pending.remove(req)
            self.tracer.end("queued", req.rid)
            self.tracer.begin("staged", req.rid)
            meta = self._rag_meta.pop(req.rid, None)
            if meta is not None:
                # chunk-reuse accounting: of the retrieved-chunk blocks
                # this assembled prompt staged, how many spliced from
                # the index instead of prefilling
                self.stats.retrieval_chunk_blocks += len(meta)
                self.stats.retrieval_chunk_hits += len(
                    set(rb.hit_idx) & set(meta))
            self._staging.append(_Staging(
                req=req, rb=rb,
                todo=_hole_spans(rb.hit_idx, int(req.prompt.size) - 1,
                                 self.block_size),
            ))
        if self.faults is not None and self.faults.fire("stage_stall"):
            # injected wedged staging round: no prefill work this
            # boundary; incomplete entries pick up at the next one
            self.stats.stage_stalls += 1
            return
        while True:
            work = [st for st in self._staging if not st.done]
            if not work:
                return
            self._stage_round(work)
            if not catch_up:
                return

    # -- preemption: spill / restore / reclaim -----------------------------
    def _spill_slot(self, i: int) -> None:
        """Preempt the request in slot ``i``: sync its generated tokens,
        copy its live KV blocks to the host spill region, release every
        pool block it owns, free the slot. Restore resumes bit-exactly:
        the KV round-trips losslessly and the position-keyed PRNG makes
        the sampled stream a pure function of (seed, position)."""
        slot = self.slots[i]
        rb = self._slot_rb[i]
        tokens = self.slot_tokens(i)
        payload = self.mgr.spill_request(rb, slot.pos)
        self.spill.stage(slot.rid)
        self.spill.commit(slot.rid, payload, payload["nbytes"])
        self._spilled.append(_Spilled(
            req=slot.req, generated=slot.generated, tokens=tokens,
            valid_end=slot.pos, n_blocks=payload["n_blocks"],
            first_t=slot.first_t,
        ))
        self._slot_rb[i] = None
        self._tables[i] = kvp.SCRATCH_BLOCK
        self._admit_pending.pop(i, None)   # dies with the slot
        self.slots[i] = _Slot()
        self.stats.preemptions += 1
        self.stats.spilled_blocks += payload["n_blocks"]

    def _unstage(self, st: _Staging) -> None:
        """Reclaim a staging entry's blocks. A fresh entry requeues to
        pending (prompt KV is recomputable); a restored spill re-spills
        — its generated KV is not recomputable from the prompt."""
        self._staging.remove(st)
        if st.resume is None:
            self.mgr.release_request(st.rb)
            self.pending.append(st.req)
            self.stats.unstaged += 1
        else:
            sp = st.resume
            payload = self.mgr.spill_request(st.rb, sp.valid_end)
            self.spill.stage(sp.req.rid)
            self.spill.commit(sp.req.rid, payload, payload["nbytes"])
            self._spilled.append(sp)
            self.stats.preemptions += 1
            self.stats.spilled_blocks += payload["n_blocks"]

    def _reclaim_for(self, score: tuple,
                     exclude_slot: int | None = None) -> bool:
        """Free pool resources for a request scoring ``score`` by
        victimizing the WORST strictly-worse holder: an unadmitted
        staging entry is unstaged, an active slot is spilled. Strict
        ordering (scores are a total order via ``seq``) means A can
        preempt B and never vice versa — no thrash, guaranteed
        progress. Returns False when no worse victim exists (the
        requester is itself the worst — it waits or self-spills)."""
        victims: list[tuple[tuple, int, object]] = []
        for st in self._staging:
            victims.append((self._score(st.req), 0, st))
        for i, slot in enumerate(self.slots):
            if i != exclude_slot and not slot.free:
                victims.append((self._score(slot.req), 1, i))
        victims = [v for v in victims if v[0] > score]
        if not victims:
            return False
        _, kind, victim = max(victims, key=lambda v: v[0])
        if kind == 0:
            self._unstage(victim)
        else:
            self._spill_slot(victim)
        return True

    def _try_restore(self) -> None:
        """Splice spilled requests back, best score first, one per free
        slot: re-acquire blocks (prefix-index hits splice bit-identical
        content; misses rewrite the host copy), re-publish, and enter
        the staged-done queue — admission treats a restore exactly like
        a fully staged arrival. A restore may itself reclaim from
        strictly worse holders; on failure the request stays spilled
        (payload untouched) for the next boundary."""
        if not self._spilled:
            return
        reserved = 0   # restores this call, each owed a free slot
        for sp in sorted(self._spilled,
                         key=lambda s: self._score(s.req)):
            if sum(s.free for s in self.slots) - reserved <= 0:
                return
            payload = self.spill.fetch(sp.req.rid)
            rb = self.mgr.restore_request(sp.req.prompt, payload)
            while rb is None and self._reclaim_for(self._score(sp.req)):
                rb = self.mgr.restore_request(sp.req.prompt, payload)
            if rb is None:
                return
            self._spilled.remove(sp)
            self.spill.release(sp.req.rid)
            self._staging.append(_Staging(
                req=sp.req, rb=rb, todo=[], resume=sp,
            ))
            self.stats.restores += 1
            self.stats.restored_blocks += sp.n_blocks

    # -- work-stealing handoff (router-level migration) --------------------
    def take_spilled(self, rid: int) -> tuple[_Spilled, dict] | None:
        """Detach a spilled request for migration to a sibling replica:
        returns its resume state and host-side KV payload (both plain
        numpy — device-agnostic), releasing the local spill-region
        reservation. The router steals work this way when another
        replica holds the victim's prefix warm (or simply has room)."""
        for sp in self._spilled:
            if sp.req.rid == rid:
                self._spilled.remove(sp)
                payload = self.spill.fetch(rid)
                self.spill.release(rid)
                return sp, payload
        return None

    def submit_spilled(self, sp: _Spilled, payload: dict) -> int:
        """Adopt a request stolen from a sibling: re-key it into THIS
        server's rid/seq space (priority, deadline and first-token time
        travel with it — SLO accounting does not reset on migration)
        and park it in the local spill region; the normal restore path
        does the rest at the next boundary."""
        rid = self._next_rid
        self._next_rid += 1
        sp.req.rid = rid
        sp.req.seq = self._seq
        self._seq += 1
        self.spill.stage(rid)
        self.spill.commit(rid, payload, payload["nbytes"])
        self._spilled.append(sp)
        return rid

    # -- admission: a block-table splice, zero dispatches ------------------
    def _admit_ready(self) -> None:
        """Move fully staged requests into free slots, best score first
        (EDF jumps the done-queue too; FIFO scores keep arrival order).
        Pure host bookkeeping — the admitted row's correction step
        (decode of ``prompt[-1]`` at position S-1, exactly the logits
        solo decode computes there) runs as its first step INSIDE the
        next segment program, so admission adds no dispatch of its own.
        The correction token parks in ``_admit_pending`` until that
        dispatch; a row preempted in between just drops its entry.

        A restored spill (``st.resume``) re-enters here with its KV
        already spliced: the slot picks up at ``valid_end`` with its
        synced tokens as a host chunk and its original first-token time
        — downstream accounting cannot tell it was ever gone."""
        ready = sorted((st for st in self._staging if st.done),
                       key=lambda st: self._score(st.req))
        free = [i for i, s in enumerate(self.slots) if s.free]
        for st in ready:
            if not free:
                return
            i = free.pop(0)
            self._staging.remove(st)
            r, sp = st.req, st.resume
            self.tracer.end("staged", r.rid)
            slot = self.slots[i]
            slot.rid = r.rid
            slot.prompt = r.prompt
            slot.sample = r.sample
            slot.key = (None if r.sample is None else
                        np.asarray(sampling.request_key(r.sample.seed)))
            slot.req = r
            if sp is None:
                self.mgr.publish_prompt(r.prompt, st.rb)
                # the first write position S-1 must be exclusively
                # owned; structurally it always is (sharing covers only
                # full prompt[:-1] blocks) — enforced, not assumed
                wb = (int(r.prompt.size) - 1) // self.block_size
                if wb < len(st.rb.bids):
                    self.mgr.ensure_exclusive(st.rb, wb)
                slot.pos = int(r.prompt.size) - 1
                slot.remaining = r.max_new
                slot.generated = 0
                slot.chunks = []
                slot.first_t = None
                tok = int(r.prompt[-1])
                self.stats.admitted += 1
            else:
                # resume: KV valid on [0, valid_end); next input token
                # is the last one generated (or prompt[-1] if preempted
                # before any) — exactly where the stream left off
                slot.pos = sp.valid_end
                slot.remaining = r.max_new - sp.generated
                slot.generated = sp.generated
                slot.chunks = ([(sp.tokens.reshape(1, -1), 0,
                                 sp.generated)] if sp.generated else [])
                slot.first_t = sp.first_t
                tok = (int(sp.tokens[-1]) if sp.generated
                       else int(r.prompt[-1]))
            self._tables[i] = st.rb.table_row(self.blocks_per_table)
            self._slot_rb[i] = st.rb
            self._admit_pending[i] = tok

    def _free_slot(self, slot_idx: int) -> None:
        rb = self._slot_rb[slot_idx]
        if rb is not None:
            self.mgr.release_request(rb)
            self._slot_rb[slot_idx] = None
        self._tables[slot_idx] = kvp.SCRATCH_BLOCK
        self._admit_pending.pop(slot_idx, None)
        super()._free_slot(slot_idx)

    # -- segment decode (admission fused in) -------------------------------
    def _paged_segment_fn(self, num_steps: int, admit_k: int) -> Callable:
        """The slab scheduler's batched segment scan, bracketed by block
        bookkeeping: gather the tables' blocks into a dense slab view
        ONCE, decode every step on it with the existing dense machinery
        (the aligned/ragged fast paths kept verbatim — paging costs O(1)
        gathers per segment, not per token), scatter the blocks back at
        the end. Plus the admission token merge: newly admitted rows
        enter the scan at their correction position, so one program
        covers admit + decode — no separate admission dispatch."""
        step = make_serve_step(self.cfg, self.api, self.minfo, self.mesh,
                               tp=self.tp)
        max_pos = self.max_len - 1
        baxes, laxes = self.mgr.pool.batch_axes, self.mgr.pool.length_axes

        def segment(params, toks, pool, pos, bt, admit_slots, admit_toks,
                    sample=None):
            if admit_k:
                toks = toks.at[admit_slots].set(admit_toks)
            dense = kvp.gather_blocks(pool, baxes, laxes, bt)
            buf = jnp.zeros((toks.shape[0], num_steps), jnp.int32)

            def body(carry, i):
                tok, dense, buf = carry
                p = jnp.minimum(pos + i, max_pos)
                nxt, dense = step(params, tok, dense, p, None, sample)
                buf = jax.lax.dynamic_update_slice(buf, nxt, (0, i))
                return (nxt, dense, buf), None

            (last, dense, buf), _ = jax.lax.scan(
                body, (toks, dense, buf),
                jnp.arange(num_steps, dtype=jnp.int32),
            )
            pool = kvp.scatter_blocks(pool, dense, baxes, laxes, bt)
            return buf, last, pool

        return jax.jit(segment, donate_argnums=(1, 2))

    def _paged_kernel_segment_fn(self, num_steps: int,
                                 admit_k: int) -> Callable:
        """The slab-free segment: decode runs IN PLACE on the block
        pool. No ``gather_blocks`` / ``scatter_blocks`` brackets — the
        scan carries the pool itself and every step's attention walks
        the block table directly (``kernels.ops.paged_attention_*``),
        so the segment's cache traffic is the ~steps × slots KV rows it
        actually touches instead of two pool-wide copies. The table is
        already sliced to the active frontier by ``_advance``, so the
        attention width tracks the longest live prefix, not
        ``max_len``. Admission merge as in the slab segment."""
        step = make_serve_step(self.cfg, self.api, self.minfo, self.mesh,
                               tp=self.tp)
        max_pos = self.max_len - 1

        def segment(params, toks, pool, pos, bt, admit_slots, admit_toks,
                    sample=None):
            if admit_k:
                toks = toks.at[admit_slots].set(admit_toks)
            buf = jnp.zeros((toks.shape[0], num_steps), jnp.int32)

            def body(carry, i):
                tok, pool, buf = carry
                p = jnp.minimum(pos + i, max_pos)
                nxt, pool = step(params, tok, pool, p, None, sample, bt)
                buf = jax.lax.dynamic_update_slice(buf, nxt, (0, i))
                return (nxt, pool, buf), None

            (last, pool, buf), _ = jax.lax.scan(
                body, (toks, pool, buf),
                jnp.arange(num_steps, dtype=jnp.int32),
            )
            return buf, last, pool

        return jax.jit(segment, donate_argnums=(1, 2))

    def _segment_table_width(self, active: list[int], steps: int) -> int:
        """Block-table width for this segment: cover the farthest
        position any active row will attend to (its write frontier
        after ``steps``), rounded up to a power of two so executable
        shapes stay few, clamped to the full table. Narrower tables
        mean the paged kernel's grid only walks blocks that can hold
        live KV."""
        frontier = max(self.slots[i].pos + steps for i in active)
        nbu = -(-frontier // self.block_size)
        nbu = 1 << max(0, (nbu - 1).bit_length())
        return max(1, min(self.blocks_per_table, nbu))

    def _segment_steps(self, active: list[int], *,
                       draining: bool = False) -> int:
        """Shrink-to-fit as in the slab scheduler, with one more reason
        to cap at ``segment``: an INCOMPLETE staging entry needs
        boundaries to interleave its chunks behind decode — that cadence
        IS the prefill-ahead overlap. (Merely having a backlog does not:
        ``_stage`` already ran this iteration, so whatever could start
        staging has, and fully staged entries just wait for a
        retirement, which is itself a boundary — capping for them would
        be pure dispatch overhead, the mistake the slab scheduler's
        hysteresis timeout exists to bound.) Parked RAG queries count
        too: overlapped retrieval runs right after this dispatch and
        the assembled prompts stage at the NEXT boundary — an uncapped
        segment would turn the overlap into an admission-latency tax
        larger than the retrieval it hides."""
        min_rem = min(self.slots[i].remaining for i in active)
        staging_wants_boundaries = (
            any(not st.done for st in self._staging)
            or bool(self._spilled)    # spills restore only at boundaries
            or bool(self._queries))   # park -> retrieve -> stage next
        entry_possible = staging_wants_boundaries or (
            not draining and any(s.free for s in self.slots))
        if entry_possible:
            return min(min_rem, self.segment)
        if min_rem <= self.segment:
            return min_rem
        return 1 << (min_rem.bit_length() - 1)

    def _grow_active(self, draining: bool,
                     steps_override: int | None = None
                     ) -> tuple[list[int], int]:
        """Grow every active row's span to cover the coming segment —
        the lazy-allocation flip side: staging allocated only the
        prompt's blocks, so each boundary must secure ``pos + steps``
        before dispatch. Best-scored rows grow first; a row that cannot
        grow reclaims from strictly worse holders, and if none exist it
        spills ITSELF (it is the worst — yielding now beats wedging the
        segment). Any membership change restarts the pass, so the
        returned (active, steps) is a fixpoint: every listed row owns
        its full segment span. Terminates: every restart consumed a
        victim, and victims are finite. ``steps_override`` fixes the
        span target instead of ``_segment_steps`` — the speculative path
        secures ONE position (its ``t_in`` write); drafted positions go
        to scratch and only accepted ones ever allocate (at commit)."""
        while True:
            active = [i for i, s in enumerate(self.slots)
                      if not s.free and s.remaining > 0]
            if not active:
                return [], 0
            steps = (steps_override if steps_override is not None
                     else self._segment_steps(active, draining=draining))
            changed = False
            for i in sorted(active,
                            key=lambda j: self._score(self.slots[j].req)):
                slot = self.slots[i]
                if slot.free:       # spilled by an earlier row's growth
                    changed = True
                    continue
                rb = self._slot_rb[i]
                need = slot.pos + steps
                ok = self.mgr.ensure_span(rb, need)
                while not ok and self._reclaim_for(
                        self._score(slot.req), exclude_slot=i):
                    changed = True
                    ok = self.mgr.ensure_span(rb, need)
                if not ok:
                    self._spill_slot(i)
                    changed = True
            if not changed:
                return active, steps

    def _advance(self, *, draining: bool = False) -> None:
        if self.faults is not None and self.faults.fire("evict_storm"):
            # injected eviction storm: every cached block force-evicted,
            # prefix index flushed — restores must survive a cold pool
            self.mgr.alloc.evict_cached()
        active_now = any(not s.free and s.remaining > 0
                         for s in self.slots)
        if self._queries and not (self.rag_overlap and active_now):
            # nothing decoding to hide behind (or overlap disabled):
            # collect/retrieve now, so the queries stage THIS boundary
            if not self.rag_overlap:
                # serial means serial — quiesce the enqueued device
                # work first (an async backlog would otherwise hide
                # retrieval behind it for free), so this arm models
                # the retrieve-then-decode pipeline the overlap path
                # beats. (With overlap on, the search already ran on
                # the I/O worker; collecting it needs no quiesce.)
                jax.block_until_ready(self._toks)
            self._drain_queries(overlapped=False)
        with self.tracer.span("stage"):
            self._stage(catch_up=not active_now)
        with self.tracer.span("admit"):
            self._admit_ready()
        self._sync_pool_stats()
        if self._spec_on:
            self._advance_spec(draining)
            return
        with self.tracer.span("grow"):
            active, steps = self._grow_active(draining)
        if not active:
            return
        # growth may have extended (or preemption rebuilt) block spans —
        # refresh the dispatched tables from the live RequestBlocks
        for i in active:
            self._tables[i] = self._slot_rb[i].table_row(
                self.blocks_per_table)
        admits = sorted(self._admit_pending.items())
        self._admit_pending.clear()
        admit_slots = [i for i, _ in admits]
        admit_toks = [t for _, t in admits]
        pos = np.full((self.num_slots,), self.max_len - 1, np.int32)
        for i in active:
            pos[i] = self.slots[i].pos
        aligned = (len(active) == self.num_slots
                   and len({self.slots[i].pos for i in active}) == 1)
        state = self._segment_sample_state(active)
        admit_k = len(admit_slots)
        if self.kernel == "paged":
            width = self._segment_table_width(active, steps)
            seg_fn = self._paged_kernel_segment_fn
        else:
            width = self.blocks_per_table
            seg_fn = self._paged_segment_fn
        # host-side guards for the drop-sentinel write path: every table
        # entry must be a real pool block (gathers promise in-bounds)
        # and every active row's write frontier must stay inside its
        # allocated span (writes past it would silently drop)
        # a copy: the host-to-device transfer of a numpy array may read
        # it after the dispatch returns, and ``self._tables`` is
        # rewritten in place at the next boundary
        bt_np = self._tables[:, :width].copy()
        kvp.validate_tables(bt_np, self.mgr.pool.num_blocks)
        for i in active:
            rb = self._slot_rb[i]
            if rb is not None:
                self.mgr.check_span(rb, self.slots[i].pos + steps)
        seg = self._compiled(
            ("pseg", self.num_slots, steps,
             "aligned" if aligned else "ragged",
             "sampled" if state is not None else "greedy",
             admit_k, self.kernel, width, self._plan_key),
            lambda: seg_fn(steps, admit_k),
        )
        pos_arg = (jnp.int32(self.slots[active[0]].pos) if aligned
                   else jnp.asarray(pos))
        bt = jnp.asarray(bt_np)
        a_slots = jnp.asarray(admit_slots, jnp.int32)
        a_toks = jnp.asarray(np.asarray(admit_toks,
                                        np.int32).reshape(-1, 1))
        t0 = self._timer()
        with self._launch(seg), kops.execution_plan(self.plan):
            buf, self._toks, self.mgr.pool.cache = seg(
                self.params, self._toks, self.mgr.pool.cache, pos_arg,
                bt, a_slots, a_toks, state,
            )
        if self.watchdog.observe(self._timer() - t0):
            self.stats.watchdog_events += 1
        if self._queries:
            # the parked queries' searches have been running on the I/O
            # worker throughout the dispatch above — collect and stage
            # them now, paying only whatever the segment didn't cover
            self._drain_queries(overlapped=True)
        self.stats.segments += 1
        self.stats.decode_steps += steps * len(active)
        self.stats.wasted_steps += steps * (self.num_slots - len(active))
        now = self._clock()
        for i in active:
            slot = self.slots[i]
            take = min(steps, slot.remaining)
            slot.chunks.append((buf, i, take))
            slot.generated += take
            slot.remaining -= take
            slot.pos += take
            if slot.first_t is None:
                # first token dispatched this segment (admitted rows'
                # correction step ran inside it)
                slot.first_t = now
            if slot.remaining == 0:
                self._retire(i)
        # re-sync after the retirements so stats read at a quiescent
        # boundary (e.g. the serving example's summary after run())
        # reflect the released blocks, not the pre-segment snapshot
        self._sync_pool_stats()

    # -- speculative decoding (launch.spec) --------------------------------
    def _hist(self, i: int) -> np.ndarray:
        """Committed token history of the request in slot ``i`` (prompt
        + accepted generations): ``hist[p]`` is the token at sequence
        index ``p``, so ``hist[slot.pos]`` is the verifier's ``t_in``."""
        slot = self.slots[i]
        return np.concatenate(
            [slot.prompt, self.slot_tokens(i)]).astype(np.int32)

    def _draft_fn(self) -> Callable:
        # the draft always runs unsharded and under the DEFAULT execution
        # plan: per-layer plan entries are keyed to the TARGET's layers
        return jax.jit(
            make_draft_program(self.spec.draft_cfg, self.draft_api,
                               self.spec.k, self.max_len),
            donate_argnums=(4,),
        )

    def _verify_fn(self) -> Callable:
        return jax.jit(
            make_verify_step(self.cfg, self.api, self.minfo, self.mesh,
                             tp=self.tp),
            donate_argnums=(2,),
        )

    def _draft_tokens(self, active: list[int]) -> np.ndarray:
        """Run the combined ingest+draft program; returns (N, k) drafts.

        Each round feeds every active row the next <= k+1 committed
        tokens past its draft frontier (``_dpos``). In steady state the
        lag is exactly last step's commit (<= k+1), so ONE dispatch
        ingests and drafts; after admission or a restore the loop runs
        catch-up rounds until every frontier reaches ``pos + 1``. Rows
        already caught up re-feed ``t_in`` as a 1-token chunk at ``pos``
        — an idempotent KV rewrite (same token, same prefix) that keeps
        the batch shape static. Only the FINAL round's drafts are used
        (every row is fully ingested by then)."""
        k = self.spec.k
        w = k + 1
        n = self.num_slots
        max_pos = self.max_len - 1
        hists = {i: self._hist(i) for i in active}
        dpos = {}
        for i in active:
            rid, dp = self._dpos.get(i, (None, 0))
            dpos[i] = dp if rid == self.slots[i].rid else 0
        fn = self._compiled(("draft", n, k), self._draft_fn)
        while True:
            chunk = np.zeros((n, w), np.int32)
            clen = np.ones((n,), np.int32)
            start = np.full((n,), max_pos, np.int32)
            final = True
            for i in active:
                pos = self.slots[i].pos
                lag = pos + 1 - dpos[i]
                if lag <= 0:
                    start[i] = pos
                    chunk[i, 0] = hists[i][pos]
                else:
                    take = min(lag, w)
                    start[i] = dpos[i]
                    chunk[i, :take] = hists[i][dpos[i]:dpos[i] + take]
                    clen[i] = take
                    dpos[i] += take
                    if dpos[i] < pos + 1:
                        final = False
            with self._launch(fn):
                drafts, self._draft_cache = fn(
                    self._draft_params, jnp.asarray(chunk),
                    jnp.asarray(clen), jnp.asarray(start), self._draft_cache)
            if final:
                break
        for i in active:
            self._dpos[i] = (self.slots[i].rid, dpos[i])
        return np.asarray(drafts)

    def _advance_spec(self, draining: bool) -> None:
        """One speculative iteration: draft k, verify k+1 in ONE rowwise
        program, accept/commit host-side. The pool only ever grows by
        ACCEPTED positions: the verifier writes drafted positions into
        the slot's private scratch rows (spliced into its table past the
        allocated span), and commit copies just the blocks the accepted
        span reaches into allocator-owned blocks — a rejected draft
        triggers no allocation and no copy."""
        k = self.spec.k
        with self.tracer.span("grow"):
            active, _ = self._grow_active(draining, steps_override=1)
        if not active:
            return
        # admitted rows' correction token comes from host history here
        # (toks[i, 0] = hist[pos]); the plain path's fused merge is moot
        self._admit_pending.clear()
        drafts = self._draft_tokens(active)
        width = self._segment_table_width(active, k + 1)
        bt_np = np.full((self.num_slots, width), kvp.SCRATCH_BLOCK,
                        np.int32)
        toks = np.zeros((self.num_slots, k + 1), np.int32)
        pos = np.full((self.num_slots,), self.max_len - 1, np.int32)
        for i in active:
            slot = self.slots[i]
            rb = self._slot_rb[i]
            row = rb.table_row(self.blocks_per_table)[:width].copy()
            # scratch splice: drafted positions past the allocated span
            # land in this slot's private spare rows (never shared, so
            # concurrent in-chunk reads through the table stay private)
            need = min(self.mgr.blocks_needed(slot.pos + k + 1), width)
            for j in range(len(rb.bids), need):
                row[j] = self._scratch[i][j - len(rb.bids)]
            bt_np[i] = row
            toks[i, 0] = self._hist(i)[slot.pos]
            toks[i, 1:] = drafts[i]
            pos[i] = slot.pos
        # no check_span here BY DESIGN: drafted writes intentionally
        # exceed the span into scratch (coverage is by construction);
        # table validity is still enforced
        kvp.validate_tables(bt_np, self.mgr.pool.num_blocks)
        state = self._segment_sample_state(active)
        vf = self._compiled(
            ("specv", self.num_slots, k, width,
             "sampled" if state is not None else "greedy",
             self._plan_key),
            self._verify_fn)
        t0 = self._timer()
        with self._launch(vf), kops.execution_plan(self.plan):
            tgt, self.mgr.pool.cache = vf(
                self.params, jnp.asarray(toks), self.mgr.pool.cache,
                jnp.asarray(pos), jnp.asarray(bt_np), state)
        if self._queries:
            # searches ran on the I/O worker behind the verify dispatch
            # (the spec path's only dispatch->sync window) — collect
            self._drain_queries(overlapped=True)
        # accept policy is host-side (the Sidebar split: flexible policy
        # on the host, static program on the accelerator) — sync here
        tgt = np.asarray(tgt)
        if self.watchdog.observe(self._timer() - t0):
            self.stats.watchdog_events += 1
        self.stats.segments += 1
        self.stats.spec_steps += 1
        rids = {i: self.slots[i].rid for i in active}
        wasted = (k + 1) * (self.num_slots - len(active))
        now = self._clock()
        for i in sorted(active, key=lambda j: self._score(self.slots[j].req)
                        if self.slots[j].req is not None else ()):
            slot = self.slots[i]
            if slot.free or slot.rid != rids[i]:
                # spilled by a better row's commit growth below — its
                # whole round is discarded; the restore redoes it
                # deterministically, so the stream stays bit-exact
                wasted += k + 1
                continue
            m = accepted_prefix(drafts[i], tgt[i])
            emit = min(m + 1, slot.remaining)
            self.stats.spec_drafted += k
            self.stats.spec_accepted += m
            rb = self._slot_rb[i]
            old_nb = len(rb.bids)
            ok = self.mgr.ensure_span(rb, slot.pos + emit)
            while not ok and self._reclaim_for(
                    self._score(slot.req), exclude_slot=i):
                ok = self.mgr.ensure_span(rb, slot.pos + emit)
            if not ok:
                # pool genuinely can't hold the accepted span: keep what
                # the existing span covers (>= 1 token — growth above
                # secured pos + 1), drop the rest; progress holds
                emit = max(1, min(emit, rb.span - slot.pos))
            new_nb = len(rb.bids)
            if new_nb > old_nb:
                dst = rb.bids[old_nb:new_nb]
                self.mgr.pool.copy_blocks(
                    dst, self._scratch[i][:len(dst)])
                kops.record_dispatch("spec_commit_copy", "dma")
                self.stats.spec_commit_copies += len(dst)
            self.stats.decode_steps += emit
            wasted += (k + 1) - emit
            slot.chunks.append((tgt[i].reshape(1, -1), 0, emit))
            slot.generated += emit
            slot.remaining -= emit
            slot.pos += emit
            if slot.first_t is None:
                slot.first_t = now
            if slot.remaining == 0:
                self._retire(i)
        self.stats.wasted_steps += wasted
        self._sync_pool_stats()
