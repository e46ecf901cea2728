"""Serving: batched prefill + scan-compiled decode.

``make_serve_step`` builds the jit-able single-token decode (the function
the decode_32k / long_500k dry-run cells lower); ``make_decode_scan``
compiles N of those steps into ONE program — a ``jax.lax.scan`` over
steps whose carry holds the running token, the (donated) KV cache, and a
preallocated output buffer written with ``dynamic_update_slice`` — so N
generated tokens cost one dispatch instead of N Python-driven dispatches.
``Server`` is a batched-request driver (prefill once, then greedy or
sampled decode — see ``launch.sampling`` for the position-keyed PRNG
rule) used by the serving example, the continuous-batching scheduler
(``launch.scheduler``), and integration tests.

``Server(plan=...)`` selects which sidebar kernel variant backs the
model's fused MLP ops: ``ExecutionMode.SIDEBAR`` (single VMEM scratch) or
``ExecutionMode.SIDEBAR_PIPELINED`` (T-deep VMEM ring — the host-side
flexible function of tile t overlaps the MXU work of up to T-1 in-flight
neighbours; the ring depth comes from the plan). The plan may be a
``LayerPlan``, an ``ExecutionMode``, a mode string, or a whole
``ExecutionPlan``. A *heterogeneous* ``ExecutionPlan`` (per-layer entries
differing from the default) is applied per layer: the layer stack is
unrolled at trace time (``cfg.scan_layers=False``) and each layer's trace
runs under ``kernels.ops.layer_scope(i)``, so ``plan.for_layer(i)``
selects that layer's kernel variant and ring depth — the planner's
per-layer depth sweep reaches the kernels. The choice is applied as
ambient state around trace time, so the same model code serves under any
variant with no signature changes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.modes import (
    ExecutionMode,
    ExecutionPlan,
    LayerPlan,
    coerce_layer_plan,
)
from repro.kernels import ops as kops
from repro.launch import sampling
from repro.launch.sampling import SamplingParams
from repro.models import layers as L
from repro.models.registry import ModelApi, get_model
from repro.parallel import tp as tplib

Array = jax.Array

# Families whose caches are pure position-masked KV: a reused buffer's
# stale tail is invisible (decode attends kpos <= pos), so prefill can
# overwrite in place. Recurrent state (ssm/hybrid/rwkv) and the audio
# decoder integrate unmasked state and need a fresh (zeroed) cache.
_CACHE_REUSE_FAMILIES = ("dense", "moe", "vlm")

# Families whose generic-transformer layer stack unrolls under
# scan_layers=False and announces kops.layer_scope — the only ones a
# heterogeneous (per-layer) ExecutionPlan can reach. vlm groups always
# scan; ssm/hybrid/audio use their own stack modules without layer_scope.
# launch.scheduler reuses this as its supported-family set (its own
# memory-free-decode constraint currently binds the same families).
PER_LAYER_PLAN_FAMILIES = ("dense", "moe")


# ---------------------------------------------------------------------------
# Tensor-parallel serving: the whole step under ONE shard_map.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TpSpec:
    """Everything the step builders need to shard_map a serve/prefill
    step over the mesh's "model" axis.

    The partitioning is classic Megatron TP driven entirely by the
    model's own ParamSpecs: every param/cache dim whose pspec mentions
    "model" is split (column-parallel wq/wk/wv/w_up/w_gate and the
    vocab-row-sharded embed table, row-parallel wo/w_down, the KV pool's
    head axis, expert stacks over experts); everything else — tokens,
    positions, block tables, per-row lengths, sampling state, MLA latent
    caches — is replicated host metadata. Inside the shard_map body the
    ambient ``parallel.tp`` context makes the model functions psum their
    row-parallel partials and all-gather the logits once per step.

    ``cfg_local`` is the per-shard view: ONLY the head counts change —
    all other shapes the forward pass derives from the (already sliced)
    arrays themselves, and global quantities (vocab_size for the padded-
    logit mask, num_experts for routing/capacity) must stay global.
    """

    mesh: Any
    axis: str                  # "model"
    size: int                  # shard count on that axis
    cfg_local: ModelConfig
    minfo: L.MeshInfo          # mesh axes WITH sizes (drives spec choices)
    param_pspecs: Any          # P tree matching the params tree
    cache_pspecs: Any          # P tree matching the cache/pool tree

    @property
    def mesh_key(self) -> tuple:
        """Hashable mesh identity for executable-cache keys."""
        return (tuple(self.mesh.devices.shape), tuple(self.mesh.axis_names))

    def param_shardings(self):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.param_pspecs)

    def cache_shardings(self):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.cache_pspecs)

    def place_params(self, params):
        return jax.device_put(params, self.param_shardings())

    def place_cache(self, cache):
        return jax.device_put(cache, self.cache_shardings())


def make_tp_spec(cfg: ModelConfig, api: ModelApi, mesh) -> TpSpec:
    """Validate cfg against the mesh and build the serving TpSpec.

    Head-axis sharding only: num_heads (and num_kv_heads for GQA) must
    divide by the model-axis size — the head-dim fallback some cache
    specs allow under GSPMD is excluded here because the paged kernels
    and the absorbed-MLA einsums want whole heads per shard. Every
    model-sharded param dim is checked for divisibility so a bad
    (config, mesh) pairing fails at construction, not inside XLA.
    """
    from repro.launch.mesh import mesh_info

    minfo = mesh_info(mesh)  # asserts the canonical axis names
    size = minfo.size("model")
    problems = []
    if cfg.num_heads % size:
        problems.append(f"num_heads {cfg.num_heads} % tp {size} != 0")
    if not cfg.use_mla and cfg.num_kv_heads % size:
        problems.append(f"num_kv_heads {cfg.num_kv_heads} % tp {size} != 0")
    specs = api.param_specs(cfg, minfo)
    flat, _ = jax.tree_util.tree_flatten_with_path(specs, is_leaf=L.is_spec)
    for path, s in flat:
        pspec = tplib.model_only_pspec(s.pspec)
        for dim, entry in zip(s.shape, tuple(pspec)):
            if entry == "model" and dim % size:
                problems.append(
                    f"param{jax.tree_util.keystr(path)}: model-sharded "
                    f"dim {dim} % tp {size} != 0"
                )
    if problems:
        raise ValueError(
            f"config {cfg.arch_id!r} cannot tensor-parallel over "
            f"{dict(minfo.sizes)}: " + "; ".join(problems)
        )
    param_pspecs = jax.tree.map(
        lambda s: tplib.model_only_pspec(s.pspec), specs, is_leaf=L.is_spec)
    # cache pspecs depend only on (cfg, minfo), never on batch/length —
    # probe with nominal sizes; the same tree serves slab caches and the
    # paged pool (identical leaf structure, batch axis = blocks).
    cache_pspecs = jax.tree.map(
        lambda s: tplib.model_only_pspec(s.pspec),
        api.cache_specs(cfg, minfo, 1, 8), is_leaf=L.is_spec)
    cfg_local = cfg
    if size > 1:
        kw = {"num_heads": cfg.num_heads // size}
        if not cfg.use_mla:
            kw["num_kv_heads"] = cfg.num_kv_heads // size
        cfg_local = dataclasses.replace(cfg, **kw)
    return TpSpec(mesh=mesh, axis="model", size=size, cfg_local=cfg_local,
                  minfo=minfo, param_pspecs=param_pspecs,
                  cache_pspecs=cache_pspecs)


def make_serve_step(cfg: ModelConfig, api: ModelApi, minfo: L.MeshInfo, mesh,
                    tp: TpSpec | None = None):
    """decode one token: (params, tokens(B,1), cache, pos[, memory, sample]).

    ``pos`` is scalar (whole batch at one length) or per-row ``(B,)``
    (the scheduler's batched segment decode over unaligned slots).
    ``sample`` is a traced per-row state from ``sampling.sample_state``
    / ``sampling.merge_rows`` — ``None`` keeps exact greedy argmax; the
    token written at sequence index ``pos + 1`` is keyed by that index
    (see ``launch.sampling`` for the position-keyed PRNG rule).
    ``block_tables`` makes ``cache`` a paged block pool decoded IN
    PLACE: writes land through the tables and attention walks them
    directly (``kernels.ops.paged_attention_*``) — the paged
    scheduler's slab-free segment path.

    ``tp`` switches the step to manual tensor parallelism: the WHOLE
    body runs under one ``shard_map`` over the mesh's "model" axis with
    params/cache partitioned per ``TpSpec`` and everything else
    replicated, the ambient ``parallel.tp`` context supplying the psums
    and the single per-step logit all-gather. The Pallas paged kernel
    traces per-shard unmodified (it sees a dense local head slice). The
    inner api call gets ``mesh=None``/no sharding hints —
    ``with_sharding_constraint`` belongs to the auto-partitioned
    (GSPMD) route, not inside a manual region.
    """

    from repro.parallel.hints import sharding_hints

    if tp is not None:
        cfg_l, minfo_l, rep = tp.cfg_local, tp.minfo, P()

        def tp_body(params, tokens, cache, pos, memory, sample,
                    block_tables):
            kw = {} if block_tables is None else {"block_tables": block_tables}
            with tplib.tensor_parallel(tp.axis, tp.size):
                logits, cache = api.decode_step(
                    params, cfg_l, tokens, cache, pos, minfo=minfo_l,
                    mesh=None, memory=memory, **kw,
                )
            logits = L.mask_pad_logits(logits, cfg.vocab_size)
            next_tok = sampling.sample_tokens(logits[:, -1, :], sample,
                                              pos + 1)
            return next_tok[:, None], cache

        def tp_serve_step(params, tokens, cache, pos, memory=None,
                          sample=None, block_tables=None):
            fn = jax.shard_map(
                tp_body, mesh=tp.mesh,
                in_specs=(tp.param_pspecs, rep, tp.cache_pspecs, rep, rep,
                          rep, rep),
                out_specs=(rep, tp.cache_pspecs),
                check_vma=False,
            )
            return fn(params, tokens, cache, pos, memory, sample,
                      block_tables)

        return tp_serve_step

    def serve_step(params, tokens, cache, pos, memory=None, sample=None,
                   block_tables=None):
        kw = {} if block_tables is None else {"block_tables": block_tables}
        with sharding_hints(mesh, minfo):
            logits, cache = api.decode_step(
                params, cfg, tokens, cache, pos, minfo=minfo, mesh=mesh,
                memory=memory, **kw,
            )
        logits = L.mask_pad_logits(logits, cfg.vocab_size)
        next_tok = sampling.sample_tokens(logits[:, -1, :], sample, pos + 1)
        return next_tok[:, None], cache

    return serve_step


def make_prefill_step(cfg: ModelConfig, api: ModelApi, minfo: L.MeshInfo,
                      mesh, tp: TpSpec | None = None):
    """Build the jit-able prompt-KV writer.

    ``cache_pos`` (scalar or per-row ``(B,)``) makes the step *chunked*:
    it writes S tokens starting at that position instead of 0, so a long
    prompt prefills as a sequence of bounded-length programs (the paged
    scheduler's prefill-ahead staging; ``Server.generate(prefill_chunk=)``
    for slab caches). ``block_tables`` routes the writes through the
    paged pool. Both default off, keeping the original signature/HLO for
    every existing caller (incl. non-transformer families that take
    neither kwarg). ``tp`` shard_maps the step exactly like
    ``make_serve_step`` — multi-token rowwise staging chunks write their
    KV through the same partitioned pool."""
    from repro.parallel.hints import sharding_hints

    if tp is not None:
        cfg_l, minfo_l, rep = tp.cfg_local, tp.minfo, P()

        def tp_body(params, batch, cache, sample, cache_pos, block_tables):
            kw = {}
            if cache_pos is not None:
                kw["cache_pos"] = cache_pos
            if block_tables is not None:
                kw["block_tables"] = block_tables
            with tplib.tensor_parallel(tp.axis, tp.size):
                logits, cache = api.prefill(
                    params, cfg_l, batch, cache, minfo=minfo_l, mesh=None,
                    **kw,
                )
            logits = L.mask_pad_logits(logits, cfg.vocab_size)
            idx = batch["tokens"].shape[1]
            if cache_pos is not None:
                idx = cache_pos + idx
            next_tok = sampling.sample_tokens(logits[:, -1, :], sample, idx)
            return next_tok[:, None], cache

        def tp_prefill_step(params, batch, cache, sample=None,
                            cache_pos=None, block_tables=None):
            fn = jax.shard_map(
                tp_body, mesh=tp.mesh,
                in_specs=(tp.param_pspecs, rep, tp.cache_pspecs, rep, rep,
                          rep),
                out_specs=(rep, tp.cache_pspecs),
                check_vma=False,
            )
            return fn(params, batch, cache, sample, cache_pos, block_tables)

        return tp_prefill_step

    def prefill_step(params, batch, cache, sample=None, cache_pos=None,
                     block_tables=None):
        kw = {}
        if cache_pos is not None:
            kw["cache_pos"] = cache_pos
        if block_tables is not None:
            kw["block_tables"] = block_tables
        with sharding_hints(mesh, minfo):
            logits, cache = api.prefill(
                params, cfg, batch, cache, minfo=minfo, mesh=mesh, **kw
            )
        logits = L.mask_pad_logits(logits, cfg.vocab_size)
        # prefill of S tokens starting at p emits the token at sequence
        # index p + S (p = 0 for the classic whole-prompt prefill)
        idx = batch["tokens"].shape[1]
        if cache_pos is not None:
            idx = cache_pos + idx
        next_tok = sampling.sample_tokens(logits[:, -1, :], sample, idx)
        return next_tok[:, None], cache

    return prefill_step


def make_verify_step(cfg: ModelConfig, api: ModelApi, minfo: L.MeshInfo,
                     mesh, tp: TpSpec | None = None):
    """Build the speculative-decode verifier: one batched rowwise program.

    ``verify(params, tokens (B, K+1), cache, pos (B,), block_tables,
    sample=None) -> (target (B, K+1), cache)``. Row r's chunk is its last
    committed token followed by K drafted tokens; the chunk writes KV at
    positions ``pos[r] .. pos[r]+K`` through the block table (drafted
    positions land in the row's private scratch blocks — the table splice
    is the caller's job) and ``target[r, i]`` is the token the target
    model emits at sequence index ``pos[r] + 1 + i``, sampled with the
    position-keyed PRNG (greedy rows: exact argmax). Comparing drafts
    against ``target`` host-side therefore reproduces the plain decode
    stream exactly: position ``pos+1`` is always plain decode's token,
    and each later position is too whenever every draft before it
    matched. This is the PR-5 multi-token rowwise prefill with
    ``all_logits=True`` — only transformer families (dense/moe) support
    it; with MoE, co-batched positions share expert capacity, so serve
    with a no-drop ``capacity_factor`` for bit-parity (same caveat as
    chunked prefill). ``tp`` shard_maps the body exactly like
    ``make_prefill_step``.
    """
    from repro.parallel.hints import sharding_hints

    if tp is not None:
        cfg_l, minfo_l, rep = tp.cfg_local, tp.minfo, P()

        def tp_body(params, tokens, cache, pos, block_tables, sample):
            with tplib.tensor_parallel(tp.axis, tp.size):
                logits, cache = api.prefill(
                    params, cfg_l, {"tokens": tokens}, cache, minfo=minfo_l,
                    mesh=None, cache_pos=pos, block_tables=block_tables,
                    all_logits=True,
                )
            logits = L.mask_pad_logits(logits, cfg.vocab_size)
            target = sampling.sample_token_block(logits, sample, pos)
            return target, cache

        def tp_verify_step(params, tokens, cache, pos, block_tables,
                           sample=None):
            fn = jax.shard_map(
                tp_body, mesh=tp.mesh,
                in_specs=(tp.param_pspecs, rep, tp.cache_pspecs, rep, rep,
                          rep),
                out_specs=(rep, tp.cache_pspecs),
                check_vma=False,
            )
            return fn(params, tokens, cache, pos, block_tables, sample)

        return tp_verify_step

    def verify_step(params, tokens, cache, pos, block_tables, sample=None):
        with sharding_hints(mesh, minfo):
            logits, cache = api.prefill(
                params, cfg, {"tokens": tokens}, cache, minfo=minfo,
                mesh=mesh, cache_pos=pos, block_tables=block_tables,
                all_logits=True,
            )
        logits = L.mask_pad_logits(logits, cfg.vocab_size)
        target = sampling.sample_token_block(logits, sample, pos)
        return target, cache

    return verify_step


def make_decode_scan(cfg: ModelConfig, api: ModelApi, minfo: L.MeshInfo,
                     mesh, num_steps: int,
                     tp: TpSpec | None = None) -> Callable:
    """``num_steps`` decode steps as one compiled program.

    Returns ``decode_scan(params, tok, cache, pos, memory=None,
    sample=None) -> (tokens (B, num_steps), cache)``. The scan carry is
    (running token, cache, output buffer): the cache threads through the
    carry so jit donation aliases it across all steps, and each step's
    token lands in the preallocated buffer via ``dynamic_update_slice``
    — no per-token host round-trip, no restacked ys. Sampling keys are
    folded from (request key, token position) inside the step, so the
    scan needs no PRNG carry and matches the loop decode bit-for-bit.
    Under ``tp`` the scanned step is the shard_mapped one; the sharded
    cache rides the carry with matched in/out specs, so no per-step
    resharding ever appears in the program.
    """
    step = make_serve_step(cfg, api, minfo, mesh, tp=tp)

    def decode_scan(params, tok, cache, pos, memory=None, sample=None):
        b = tok.shape[0]
        buf = jnp.zeros((b, num_steps), jnp.int32)

        def body(carry, i):
            tok, cache, buf = carry
            nxt, cache = step(params, tok, cache, pos + i, memory, sample)
            buf = jax.lax.dynamic_update_slice(buf, nxt, (0, i))
            return (nxt, cache, buf), None

        (_, cache, buf), _ = jax.lax.scan(
            body, (tok, cache, buf), jnp.arange(num_steps, dtype=jnp.int32)
        )
        return buf, cache

    return decode_scan


@dataclasses.dataclass
class ServeResult:
    tokens: Any           # (B, prompt+generated)
    prompt_len: int
    generated: int


class Server:
    """Minimal batched decoding server (scan-compiled; greedy by
    default, sampled via ``generate(sample=SamplingParams(...))``)."""

    def __init__(self, cfg: ModelConfig, params, *, mesh=None,
                 max_len: int = 256,
                 execution_mode: ExecutionMode | str | None = None,
                 plan: LayerPlan | ExecutionPlan | ExecutionMode | str |
                 None = None,
                 ) -> None:
        self.params = params
        self.mesh = mesh
        self.minfo = L.HOST
        self.max_len = max_len
        if plan is not None and execution_mode is not None:
            raise ValueError("pass either plan= or execution_mode=, not both")
        if plan is None:
            plan = (ExecutionMode.SIDEBAR if execution_mode is None
                    else execution_mode)
        if isinstance(plan, ExecutionPlan):
            base = plan.default
            if not plan.is_uniform:
                # Per-layer kernel variants need one trace per layer: a
                # scanned stack traces its body once and would flatten
                # the plan to its default. Trade HLO size for dispatch.
                # Only the generic transformer's dense/moe stacks unroll
                # under scan_layers=False and announce layer_scope; fail
                # loudly elsewhere instead of silently serving the
                # default for every layer.
                if cfg.family not in PER_LAYER_PLAN_FAMILIES:
                    raise ValueError(
                        "a heterogeneous (per-layer) ExecutionPlan is "
                        "realized by unrolling the transformer layer "
                        f"stack; family {cfg.family!r} traces a single "
                        "variant — pass a uniform plan or a LayerPlan"
                    )
                cfg = dataclasses.replace(cfg, scan_layers=False)
        else:
            plan = base = coerce_layer_plan(plan)
        if base.mode not in (
            ExecutionMode.SIDEBAR, ExecutionMode.SIDEBAR_PIPELINED
        ):
            raise ValueError(
                "Server serves through the sidebar fast path; "
                "the plan's (default) mode must be SIDEBAR or "
                f"SIDEBAR_PIPELINED, got {base.mode}"
            )
        self.cfg = cfg
        self.api = get_model(cfg)
        self.plan = plan
        self.execution_mode = base.mode
        # mesh => tensor-parallel serving: the step programs run under
        # shard_map with params/caches partitioned on the "model" axis
        self.tp = make_tp_spec(cfg, self.api, mesh) if mesh is not None \
            else None
        if self.tp is not None:
            self.minfo = self.tp.minfo
            self.params = self.tp.place_params(params)
        self._mesh_key = self.tp.mesh_key if self.tp is not None else None
        self._prefill = jax.jit(
            make_prefill_step(cfg, self.api, self.minfo, mesh, tp=self.tp),
            donate_argnums=(2,),
        )
        self._decode = jax.jit(
            make_serve_step(cfg, self.api, self.minfo, mesh, tp=self.tp),
            donate_argnums=(2,),
        )
        # executable cache: one compiled decode program per (step count,
        # mesh identity) — jit itself re-specializes on batch; repeat
        # traffic of the same (batch, gen) shape never re-traces, and a
        # server on a different mesh can never reuse a stale program.
        self._decode_scans: dict[tuple, Callable] = {}
        self._cache_pool: dict[int, Any] = {}

    # -- KV-cache pooling --------------------------------------------------
    def _take_cache(self, b: int):
        """A (B, max_len) cache: pooled buffer when the family's cache is
        position-masked KV (prefill overwrites, decode masks the stale
        tail), freshly zero-initialized otherwise."""
        if self.cfg.family in _CACHE_REUSE_FAMILIES:
            pooled = self._cache_pool.pop(b, None)
            if pooled is not None:
                return pooled
        cache = self.api.init_cache(self.cfg, self.minfo, b, self.max_len)
        return self.tp.place_cache(cache) if self.tp is not None else cache

    def _return_cache(self, b: int, cache) -> None:
        if self.cfg.family in _CACHE_REUSE_FAMILIES:
            self._cache_pool[b] = cache

    def _decode_scan(self, num_steps: int) -> Callable:
        key = (num_steps, self._mesh_key)
        fn = self._decode_scans.get(key)
        if fn is None:
            fn = jax.jit(
                make_decode_scan(self.cfg, self.api, self.minfo, self.mesh,
                                 num_steps, tp=self.tp),
                donate_argnums=(2,),
            )
            self._decode_scans[key] = fn
        return fn

    def generate(self, prompts: Array, num_tokens: int,
                 extra: dict | None = None, *,
                 decode: str = "scan",
                 sample: SamplingParams | None = None,
                 prefill_chunk: int | None = None) -> ServeResult:
        """prompts: (B, S) int32 — one bucket; decode num_tokens.

        ``decode="scan"`` (default) runs all steps as one compiled
        program; ``decode="loop"`` keeps the PR-2 one-dispatch-per-token
        Python loop (benchmark baseline — token-for-token identical).
        ``sample`` switches greedy argmax to temperature / top-k / top-p
        sampling with a position-keyed PRNG stream per batch row: the
        same seed reproduces the same tokens under scan and loop decode
        alike, and temperature 0 is bit-identical to greedy.
        ``prefill_chunk`` splits the prompt's KV build into bounded
        chunks (each written at its true offset) — token-for-token
        identical to whole-prompt prefill, and the building block the
        paged scheduler's prefill-ahead staging interleaves behind
        decode. (MoE caveat: under a dropping capacity factor, chunk
        boundaries — like bucket padding — change which tokens compete
        for expert capacity; serve MoE no-drop for bit-parity.)
        """
        if decode not in ("scan", "loop"):
            raise ValueError(f"decode must be 'scan' or 'loop', got {decode!r}")
        b, s = prompts.shape
        if s + num_tokens > self.max_len:
            raise ValueError(
                f"prompt {s} + generate {num_tokens} exceeds max_len "
                f"{self.max_len}"
            )
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got "
                                 f"{prefill_chunk}")
            if self.cfg.family not in PER_LAYER_PLAN_FAMILIES:
                raise ValueError(
                    "chunked prefill needs a prefill that takes a "
                    "cache_pos offset (the generic transformer's dense/"
                    f"moe stacks); family {self.cfg.family!r} does not"
                )
        state = sampling.sample_state(sample, b) if sample is not None else None
        cache = self._take_cache(b)
        batch = {"tokens": prompts, **(extra or {})}
        # ambient kernel-variant selection must wrap trace time (the first
        # _prefill/_decode call below traces the model through kops)
        with kops.execution_plan(self.plan):
            memory = None
            if self.cfg.family == "audio":
                from repro.models import whisper as W

                memory = W.encode(self.params, self.cfg, batch["frames"])
            if self.cfg.family == "vlm":
                memory = batch.get("image_embeds")
            if prefill_chunk is not None and s > prefill_chunk:
                c0 = 0
                while c0 < s:
                    c1 = min(c0 + prefill_chunk, s)
                    chunk = dict(batch, tokens=prompts[:, c0:c1])
                    nxt, cache = self._prefill(
                        self.params, chunk, cache, state, jnp.int32(c0))
                    c0 = c1
            else:
                nxt, cache = self._prefill(self.params, batch, cache, state)
            pieces = [prompts, nxt]
            steps = num_tokens - 1
            if steps > 0 and decode == "scan":
                buf, cache = self._decode_scan(steps)(
                    self.params, nxt, cache, jnp.int32(s), memory, state
                )
                pieces.append(buf)
            elif steps > 0:
                pos = s
                for _ in range(steps):
                    nxt, cache = self._decode(
                        self.params, nxt, cache, jnp.int32(pos), memory, state
                    )
                    pieces.append(nxt)
                    pos += 1
        self._return_cache(b, cache)
        return ServeResult(
            tokens=jnp.concatenate(pieces, axis=1), prompt_len=s,
            generated=num_tokens,
        )
