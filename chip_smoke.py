"""Chip smoke: the paged continuous-batching server on a TPU at
Nemotron-4-15B widths, with the Pallas kernels on.

    python chip_smoke.py             # one chip: 8 of 32 layers, vocab 32,000
    python chip_smoke.py --chips 4   # (1, 4) tensor-parallel mesh, full model

Every width of ``configs.get_config("nemotron-4-15b")`` is kept (d 6144,
48 heads and 8 KV heads of dim 128, d_ff 24576, squared ReLU, bf16
weights and KV); on one chip the depth and the vocabulary are cut to
fit its 16 GB. Weights are random, made on the device from ``--seed``.
Eight seeded requests share a 1,024-token prefix, add 64-512 tokens of
their own and ask for 64 new tokens each; they go through ``submit()``
and ``run()`` (the first one alone, so that its published prefix blocks
are there for the other seven to splice).

The run fails unless every request completes, the KV pool drains,
prefix hits are above 0, every ``sidebar_mlp`` and ``paged_attention``
dispatch took its Pallas kernel, and the logits of each request's first
decode step match a plain forward of the same parameters (the XLA path,
``use_pallas=False``, highest matmul precision) within ``LOGIT_TOL``.
The last line of standard output is one JSON object, ``"ok": true``
only after every check passed on a TPU. Off a TPU the script exits
non-zero before it builds anything.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch.scheduler import PagedContinuousBatchingServer  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models.registry import get_model  # noqa: E402
from repro.parallel.hints import sharding_hints  # noqa: E402

ARCH = "nemotron-4-15b"
# One chip holds 16 GB. At full width one layer is ~390 M parameters
# (0.78 GB in bf16), so 8 of 32 layers and an eighth of the vocabulary
# (32,000 of 256,000) leave about 9 GB for the KV pool and activations.
ONE_CHIP_CUTS = {"num_layers": 8, "vocab_size": 32_000}
# Device memory kept free of the KV pool: the step programs' temporaries
# (prefill attention logits, the gathered prefill KV view, and the
# decode segment's copies of one layer's pool slice in and out of the
# paged kernel, about 3.5/num_layers of the pool), the compiled programs
# themselves, and the allocator's fragmentation.
HEADROOM_BYTES = 4 * 2**30
# Relative L2 distance between a request's served first-step logits and
# the plain forward's. Both run the same bf16 weights and bf16 KV; they
# differ in where bf16 rounding happens (the fused MLP keeps the
# intermediate in fp32, the paged kernel runs its softmax in fp32). At
# bf16's 2^-9 relative rounding per op, a few such differences per
# layer accumulate to about 1e-2 after 8-32 layers; a wrong head, block
# or scale mapping gives a distance near 1.
LOGIT_TOL = 5e-2
KERNEL_OPS = ("sidebar_mlp", "paged_attention")


@dataclasses.dataclass(frozen=True)
class Traffic:
    requests: int = 8
    slots: int = 8
    block_size: int = 16
    prefix: int = 1024            # shared system prompt, tokens
    own: tuple[int, int] = (64, 512)  # each request's own tokens, inclusive
    new_tokens: int = 64
    prefill_chunk: int = 256
    segment: int = 8

    @property
    def max_len(self) -> int:
        return self.prefix + self.own[1] + self.new_tokens


def smoke_config(chips: int) -> tuple[ModelConfig, dict]:
    """The served config and the cuts made to it (``reduced``)."""
    full = configs.get_config(ARCH)
    cfg = dataclasses.replace(full, use_pallas=True)
    if chips > 1:
        return cfg, {}
    reduced = {k: f"{v} of {getattr(full, k)}"
               for k, v in ONE_CHIP_CUTS.items()}
    return dataclasses.replace(cfg, **ONE_CHIP_CUTS), reduced


def make_prompts(vocab: int, traffic: Traffic, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, traffic.prefix, dtype=np.int32)
    lo, hi = traffic.own
    return [
        np.concatenate([prefix, rng.integers(0, vocab, int(n),
                                             dtype=np.int32)])
        for n in rng.integers(lo, hi + 1, traffic.requests)
    ]


def kv_bytes_per_token(cfg: ModelConfig, tp: int = 1) -> int:
    """K and V of one position over every layer, on one chip."""
    item = jnp.dtype(cfg.kv_cache_dtype).itemsize
    return 2 * cfg.num_layers * (cfg.num_kv_heads // tp) * cfg.head_dim * item


def pool_blocks(cfg: ModelConfig, traffic: Traffic, *, capacity: int,
                weight_bytes: int, tp: int = 1) -> int:
    """KV pool blocks that fit what the weights leave of one chip's
    ``capacity``, less ``HEADROOM_BYTES``."""
    block = traffic.block_size * kv_bytes_per_token(cfg, tp)
    free = capacity - weight_bytes - HEADROOM_BYTES
    need = traffic.requests * traffic.max_len // traffic.block_size
    if free < need * block:
        raise SystemExit(
            f"{free} bytes left for the KV pool; the traffic needs "
            f"{need * block}")
    return int(free // block)


def init_params(cfg: ModelConfig, seed: int, shardings=None):
    """Random weights made on the device in one program, each leaf in
    its own dtype and, with ``shardings``, already partitioned."""
    api = get_model(cfg)
    specs = api.param_specs(cfg, L.HOST)
    return L.materialize(jax.random.PRNGKey(seed), specs,
                         shardings=shardings)


def reference_logits(cfg: ModelConfig, params, prompts: list[np.ndarray],
                     mesh=None) -> np.ndarray:
    """The plain forward's logits at each prompt's last position: the
    XLA path (no Pallas kernel, no KV cache, no paging) at the highest
    matmul precision, one request at a time on a common padded length
    (causal, so the padding changes nothing before it). With ``mesh``
    the parameters stay partitioned as given and GSPMD partitions the
    forward over the same devices."""
    xla = dataclasses.replace(cfg, use_pallas=False)
    api = get_model(xla)
    minfo = L.HOST
    if mesh is not None:
        from repro.launch.mesh import mesh_info
        minfo = mesh_info(mesh)
    width = max(p.size for p in prompts)

    @jax.jit
    def last_logits(params, tokens, last):
        with sharding_hints(mesh, minfo), \
                jax.default_matmul_precision("highest"):
            logits = api.forward(params, xla, {"tokens": tokens},
                                 minfo=minfo, mesh=mesh)
        return logits[0, last, :cfg.vocab_size]

    out = []
    for p in prompts:
        tokens = np.zeros((1, width), np.int32)
        tokens[0, :p.size] = p
        out.append(np.asarray(last_logits(params, tokens, p.size - 1)))
    return np.stack(out)


def _capture_first_steps(srv, prompts: list[np.ndarray], vocab: int) -> dict:
    """Bring back to the host the logits of each request's first decode
    step: its last prompt token, at position ``len - 1``, decoded by the
    server's own segment program. The decode step is wrapped before the
    server compiles it; a ``lax.cond`` sends only steps that hold such a
    row, and each (position, token) pair names one request."""
    wpos = np.array([p.size - 1 for p in prompts], np.int32)
    wtok = np.array([p[-1] for p in prompts], np.int32)
    if len(set(zip(wpos.tolist(), wtok.tolist()))) != len(prompts):
        raise SystemExit("two requests share a last (position, token)")
    got: dict[int, np.ndarray] = {}

    def sink(pos, tok, logits):
        for r in range(pos.shape[0]):
            hit = np.flatnonzero((wpos == pos[r]) & (wtok == tok[r]))
            if hit.size and int(hit[0]) not in got:
                got[int(hit[0])] = np.asarray(logits[r], np.float32)

    real = srv.api.decode_step

    def decode_step(params, cfg, tokens, cache, pos, **kw):
        logits, cache = real(params, cfg, tokens, cache, pos, **kw)
        pos_b = jnp.broadcast_to(pos, tokens.shape[:1])
        tok = tokens[:, 0]
        first = jnp.any((pos_b[:, None] == wpos[None])
                        & (tok[:, None] == wtok[None]))
        jax.lax.cond(
            first,
            lambda: jax.debug.callback(sink, pos_b, tok,
                                       logits[:, -1, :vocab]),
            lambda: None)
        return logits, cache

    srv.api = dataclasses.replace(srv.api, decode_step=decode_step)
    return got


class _CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed from
    its own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __enter__(self) -> "_CompileClock":
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration


def serve(cfg: ModelConfig, params, prompts: list[np.ndarray],
          traffic: Traffic, *, num_blocks: int, mesh=None) -> dict:
    """Drive the paged server once; returns what the checks read."""
    srv = PagedContinuousBatchingServer(
        cfg, params, mesh=mesh, num_slots=traffic.slots,
        max_len=traffic.max_len, block_size=traffic.block_size,
        num_blocks=num_blocks, prefill_chunk=traffic.prefill_chunk,
        segment=traffic.segment, kernel="paged",
    )
    first = _capture_first_steps(srv, prompts, cfg.vocab_size)
    dispatches: list = []
    t0 = time.perf_counter()
    with kops.record_dispatches(dispatches):
        srv.submit(prompts[0], traffic.new_tokens)
        srv.step()
        for p in prompts[1:]:
            srv.submit(p, traffic.new_tokens)
        done = srv.run()
    jax.block_until_ready(srv.mgr.pool.cache)
    wall = time.perf_counter() - t0
    return {
        "done": done, "wall_s": wall, "first_logits": first,
        "dispatches": dispatches, "in_use": srv.mgr.alloc.in_use,
        "prefix_hits": srv.stats.prefix_block_hits,
        "executables": len(srv.executable_cache_keys()),
    }


def check(result: dict, ref: np.ndarray, traffic: Traffic) -> list[str]:
    """Every failed check, as a line; empty when the run is good."""
    bad = []
    done = result["done"]
    if len(done) != traffic.requests:
        bad.append(f"{len(done)} of {traffic.requests} requests completed")
    short = [r.rid for r in done if r.generated != traffic.new_tokens]
    if short:
        bad.append(f"requests {short} stopped short of "
                   f"{traffic.new_tokens} tokens")
    if result["in_use"]:
        bad.append(f"pool holds {result['in_use']} blocks after the drain")
    if result["prefix_hits"] <= 0:
        bad.append("no prefix-cache hits")
    recs = [r for r in result["dispatches"] if r.op in KERNEL_OPS]
    for op in KERNEL_OPS:
        if not any(r.op == op for r in recs):
            bad.append(f"no {op} dispatch was traced")
    fell = sorted({(r.op, r.variant) for r in recs if not r.used_kernel})
    if fell:
        bad.append(f"dispatches without the Pallas kernel: {fell}")
    for i, want in enumerate(ref):
        got = result["first_logits"].get(i)
        if got is None:
            bad.append(f"request {i}: first decode step never captured")
            continue
        err = logit_error(got, want)
        if not err <= LOGIT_TOL:
            bad.append(f"request {i}: logit error {err} > {LOGIT_TOL}")
    return bad


def logit_error(got: np.ndarray, want: np.ndarray) -> float:
    """Relative L2 distance of served logits from the reference."""
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def run(cfg: ModelConfig, traffic: Traffic, *, seed: int, num_blocks: int,
        mesh=None, param_shardings=None, log=print) -> list[str]:
    """Reference first, then the server (so the pool never shares the
    chip with the reference's temporaries); returns the failed checks."""
    with _CompileClock() as clock:
        t0 = time.perf_counter()
        params = init_params(cfg, seed, param_shardings)
        jax.block_until_ready(params)
        log(f"init_s {time.perf_counter() - t0}")
        prompts = make_prompts(cfg.vocab_size, traffic, seed)
        t0 = time.perf_counter()
        ref = reference_logits(cfg, params, prompts, mesh)
        log(f"reference_s {time.perf_counter() - t0}")
        before = clock.seconds
        result = serve(cfg, params, prompts, traffic,
                       num_blocks=num_blocks, mesh=mesh)
        log(f"serve_compile_s {clock.seconds - before}")
    tokens = sum(r.generated for r in result["done"])
    errs = [logit_error(result["first_logits"][i], ref[i])
            for i in range(len(ref)) if i in result["first_logits"]]
    log(f"serve_executables {result['executables']}")
    log(f"tokens {tokens} wall_s {result['wall_s']} "
        f"prefix_block_hits {result['prefix_hits']}")
    kinds = sorted({(r.op, r.variant, r.used_kernel)
                    for r in result["dispatches"]})
    log(f"dispatches {kinds}")
    log(f"logit_rel_l2 max {max(errs, default=float('nan'))} "
        f"all {errs} tol {LOGIT_TOL}")
    log(f"compile_s_total {clock.seconds}")
    return check(result, ref, traffic)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device {json.dumps(device)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile_cache {enable_compile_cache()}")
    cfg, reduced = smoke_config(args.chips)
    traffic = Traffic()
    print(f"reduced {json.dumps(reduced)}")
    print(f"config d_model={cfg.d_model} heads={cfg.num_heads} "
          f"kv_heads={cfg.num_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} layers={cfg.num_layers} vocab={cfg.vocab_size}")
    print(f"traffic {dataclasses.asdict(traffic)}")

    mesh = shardings = None
    tp = args.chips
    if tp > 1:
        from repro.launch.mesh import make_serving_mesh
        from repro.launch.serve import make_tp_spec

        mesh = make_serving_mesh((1, tp))
        shardings = make_tp_spec(cfg, get_model(cfg), mesh).param_shardings()
    weight_bytes = L.param_bytes(get_model(cfg).param_specs(cfg, L.HOST)) // tp
    capacity = dev.memory_stats()["bytes_limit"]
    num_blocks = pool_blocks(cfg, traffic, capacity=capacity,
                             weight_bytes=weight_bytes, tp=tp)
    print(f"memory bytes_limit {capacity} weights_per_chip {weight_bytes} "
          f"headroom {HEADROOM_BYTES} pool_blocks {num_blocks} "
          f"pool_bytes_per_chip "
          f"{num_blocks * traffic.block_size * kv_bytes_per_token(cfg, tp)}",
          flush=True)
    failed = run(cfg, traffic, seed=args.seed, num_blocks=num_blocks,
                 mesh=mesh, param_shardings=shardings,
                 log=lambda s: print(s, flush=True))
    peak = max(d.memory_stats()["peak_bytes_in_use"]
               for d in jax.devices()[:tp])
    print(f"peak_bytes_in_use {peak}")
    for line in failed:
        print(f"FAILED {line}", file=sys.stderr)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
