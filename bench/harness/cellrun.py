"""One run of one cell: set-up, the measured window, the metrics, and
the comparison that decides ``correct``.

The system under test is the program's ``PagedContinuousBatchingServer``
with the paged decode kernel and the fused MLP kernels on, bf16 weights
and KV, driven through ``submit()`` / ``step()``.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from harness import check, driver, manifest, model, peaks, traffic, weights


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def process_age() -> float:
    """Seconds since this process started (from /proc; wall clock)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def devices(chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def compile_cache(root: Path) -> str:
    """The persistent compile cache, at a fixed place in the checkout,
    handed to the program's own switch through the environment."""
    import jax

    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from repro.launch.compile_cache import enable_compile_cache

    return enable_compile_cache()


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and the
    persistent cache's hits and misses, from its monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.counts: dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._count)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration

    def _count(self, event: str, **_) -> None:
        if "compilation_cache" in event:
            self.counts[event] = self.counts.get(event, 0) + 1


@dataclasses.dataclass
class Server:
    """The mix's server settings."""

    slots: int
    block_size: int
    prefill_chunk: int
    segment: int
    max_len: int
    headroom_bytes: int
    stage_ahead: int | None = None

    @classmethod
    def of(cls, mix: dict) -> "Server":
        s = dict(mix["server"])
        bs = int(s["block_size"])
        s["max_len"] = -(-traffic.max_len(mix) // bs) * bs
        s["headroom_bytes"] = int(s.pop("headroom_gib") * 2**30)
        return cls(**s)


def capacity(dev) -> int:
    """Bytes of device memory JAX may use on ``dev``."""
    return int(dev.memory_stats()["bytes_limit"])


def peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest of ``devs``."""
    return int(max(d.memory_stats()["peak_bytes_in_use"] for d in devs))


def device_bytes(tree, devs) -> list[int]:
    """Bytes of ``tree``'s arrays held on each of ``devs``."""
    import jax

    held = dict.fromkeys(devs, 0)
    for x in jax.tree.leaves(tree):
        for shard in x.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return [held[d] for d in devs]


def build(cell: manifest.Cell, seed: int, devs: list, log):
    """Weights from the seed, and the server over a pool that fills what
    the weights leave of the fullest chip, less the headroom; a block
    costs what the program's own cache specs say one chip holds of it.
    On more than one chip the server runs the program's tensor-parallel
    path over a (1, chips) mesh, each weight drawn straight into the
    program's own placement."""
    import jax

    from repro.launch.scheduler import PagedContinuousBatchingServer
    from repro.models import layers as L
    from repro.models.registry import get_model

    st = Server.of(cell.traffic)
    pcfg = model.program_config(cell.config)
    api = get_model(pcfg)
    # ``local``: the configuration as one chip holds it (its share of
    # the heads), whose cache specs size a pool block on each chip
    mesh, minfo, shardings, local = None, L.HOST, None, pcfg
    if len(devs) > 1:
        from repro.launch.mesh import make_serving_mesh
        from repro.launch.serve import make_tp_spec

        mesh = make_serving_mesh((1, len(devs)))
        if set(mesh.devices.flat) != set(devs):
            raise NoChip(f"the mesh's devices {mesh.devices.flat} are not "
                         f"the cell's {devs}")
        tp = make_tp_spec(pcfg, api, mesh)
        minfo, shardings = tp.minfo, tp.param_shardings()
        local = tp.cfg_local
    t = time.perf_counter()
    params = model.program_params(cell.config, api, pcfg, seed, minfo,
                                  shardings)
    jax.block_until_ready(params)
    held = device_bytes(params, devs)
    wbytes = max(held)
    log(f"weights_s {time.perf_counter() - t} weight_bytes "
        f"{sum(x.nbytes for x in jax.tree.leaves(params))} per_device "
        f"{held}")
    per_block = L.param_bytes(api.cache_specs(local, L.HOST, 1,
                                              st.block_size))
    cap = min(capacity(d) for d in devs)
    blocks = int((cap - wbytes - st.headroom_bytes) // per_block)
    log(f"pool bytes_limit {cap} blocks {blocks} bytes_per_device "
        f"{blocks * per_block} tokens {blocks * st.block_size} "
        f"max_len {st.max_len}")
    srv = PagedContinuousBatchingServer(
        pcfg, params, mesh=mesh, num_slots=st.slots, max_len=st.max_len,
        block_size=st.block_size, num_blocks=blocks,
        prefill_chunk=st.prefill_chunk, segment=st.segment,
        stage_ahead=st.stage_ahead, kernel="paged")
    return srv, st


def reference_weights(cell: manifest.Cell, seed: int, devs: list) -> dict:
    """The plain reference's weights: the same draw, placed by the
    benchmark's own rule over the cell's chips (each leaf's last
    dimension split), so that no leaf is whole on one chip."""
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(devs), ("chips",))
    return weights.make(cell.config, seed, weights.spread(
        weights.layout(cell.config), mesh, "chips"))


def warm_up(srv, mix: dict, *, vocab: int, seed: int,
            rate: float | None, compiling: CompileClock) -> driver.Client:
    """Serve the cell's own traffic, on a stream of the seed that the
    window does not use, for ``warm_s`` seconds: the shapes the traffic
    hits are compiled or loaded from the cache, and the batch and the
    prefix cache are in their steady state. Its clock stands still while
    JAX compiles, so that no backlog that a deployment would not have
    piles up for the window, and every run warms up for the same amount
    of traffic."""
    client = driver.Client(srv, traffic.make(mix, vocab=vocab, seed=seed,
                                             stream=1, rate=rate),
                           clock=lambda: time.perf_counter()
                           - compiling.seconds)
    client.run(until=float(mix["warm_s"]), follow=0.0)
    return client


def stats_of(srv) -> dict:
    return dataclasses.asdict(srv.stats)


def pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


@dataclasses.dataclass
class Context:
    """What a metric's reader reads."""

    cell: manifest.Cell
    server: Server
    peaks: dict
    chips: int
    window: tuple[float, float]       # client seconds
    setup_s: float
    requests: list                    # followed requests due in the window
    stopped: float                    # when the client stopped following
    client: driver.Client
    stats0: dict                      # scheduler stats at the window's open
    stats1: dict                      # and at its close
    trace: object = None              # trace.Reduction in a traced run
    trace_window: tuple[float, float] | None = None   # its client seconds
    notes: list = dataclasses.field(default_factory=list)  # earlier lines

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def kernel(self, name: str):
        return manifest.kernel(name)

    def delta(self, key: str) -> float:
        return self.stats1[key] - self.stats0[key]


def run(cell: manifest.Cell, *, seed: int, seconds: float, trace: bool,
        root: Path, log=print, rate: float | None = None,
        control: bool = False) -> dict:
    """One run; returns the result's line (a dict) and prints the
    earlier lines through ``log``. ``rate`` overrides the mix's arrival
    rate (the knee sweep). With ``control`` the tokens compared are
    those that the reference computed in float8 puts first, in place of
    the served ones (the control of the comparison; no benchmark run
    sets it); the program's own widest gap on the same sample is read
    beside it as ``program_logit_gap``."""
    import jax

    devs = devices(cell.chips)
    dev = devs[0]
    pk = peaks.lookup(dev.device_kind)
    log(f"compile_cache {compile_cache(root)}")
    clock = CompileClock()
    srv, st = build(cell, seed, devs, log)
    mix = cell.traffic
    vocab = cell.config["vocab_size"]

    t = time.perf_counter()
    warm = warm_up(srv, mix, vocab=vocab, seed=seed, rate=rate,
                   compiling=clock)
    log(f"warm_s {time.perf_counter() - t} "
        f"warm_requests {len(warm.tracks)}")
    execs0 = len(srv.executable_cache_keys())
    compile_setup = clock.seconds
    keys0 = set(srv.executable_cache_keys())

    gen = traffic.make(mix, vocab=vocab, seed=seed, stream=0, rate=rate)
    client = driver.Client(srv, gen, trace=trace)
    stats0 = stats_of(srv)
    closed = {}
    ann = trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        # device ops and the harness's own spans; no Python call events,
        # which would slow the host and swell the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level, opts.host_tracer_level = 0, 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        ann = jax.profiler.TraceAnnotation("bench.window")
        ann.__enter__()
    setup_s = process_age()
    client.t0 = client.clock()

    def on_close():
        closed["stats"] = stats_of(srv)
        closed["t"] = client.now()
        closed["compile_s"] = clock.seconds - compile_setup
        if trace:
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()

    client.run(until=seconds, follow=float(mix["follow_s"]),
               only_started=bool(mix.get("follow_only_started", False)),
               on_close=on_close)
    window = (0.0, float(seconds))
    followed = client.followed or []
    new_keys = sorted(set(srv.executable_cache_keys()) - keys0, key=repr)
    log(f"setup_s {setup_s} executables {execs0} "
        f"setup_compile_s {compile_setup} cache_events {clock.counts}")
    log(f"window_compiles {len(new_keys)} window_compile_s "
        f"{closed.get('compile_s')} keys "
        f"{[k[:8] for k in new_keys]}")
    late = client.lateness or [0.0]
    log(f"generator_lateness_s p50 {pct(late, 50)} max {max(late)}")
    log(f"requests due {len(client.window(*window))} followed "
        f"{len(followed)} backlog_at_close {len(client.backlog)} "
        f"done {sum(tr.done for tr in followed)}")

    ctx = Context(cell=cell, server=st, peaks=pk, chips=cell.chips,
                  window=window, setup_s=setup_s,
                  requests=followed, stopped=client.stopped,
                  client=client, stats0=stats0,
                  stats1=closed.get("stats", stats_of(srv)))
    if trace:
        from harness import trace as tr_mod

        t = time.perf_counter()
        planes = tr_mod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.trace = tr_mod.reduce(planes, tr_mod.span_window(planes, "window"),
                                  cell.chips)
        ctx.trace_window = (0.0, closed["t"])
        log(f"trace_read_s {time.perf_counter() - t} ops {len(ctx.trace.ops)}")
        for line in tr_mod.describe(ctx.trace):
            log(line)
    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        value = manifest.reader(m["name"])(ctx)
        if value is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    for line in ctx.notes:
        log(line)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": peak_bytes(devs)}
    breakdown = None
    if trace:
        device["busy_s"] = ctx.trace.busy_ns / 1e9
        device["window_s"] = ctx.trace.window_ns / 1e9
        breakdown = {"device_ops": ctx.trace.top_ops(10),
                     "idle_gaps": ctx.trace.top_gaps(10)}

    # the comparison, once the program's state is freed
    picked = check.sample(followed, seed, int(mix["check_tokens"]))
    failed = [tr for tr in followed if not tr.done]
    short = [tr for tr in picked if tr.tokens.size != tr.max_new]
    del srv, warm, ctx
    client.srv = None
    gc.collect()
    t = time.perf_counter()
    ref = manifest.reference(cell.config["reference"])
    w = reference_weights(cell, seed, devs)
    pad = -(-st.max_len // 128) * 128
    kw = dict(pad_to=pad, positions=traffic.max_answer(mix))
    # the control puts the float8 reference's picks in place of the
    # served tokens, and is judged by the same rule
    program, low = check.gaps(ref, w, cell.config, picked, control=control,
                              **kw)
    gaps = low if control else program
    # no request to compare reads as no number, and is not correct
    widest = max((float(g.max()) for g in gaps), default=None)
    served = sum(g.size for g in gaps)
    log(f"check requests {len(picked)} served_tokens {served} "
        f"reference_s {time.perf_counter() - t}")
    limit = float(cell.limits["logit_gap"])
    checks = {
        "logit_gap": {"value": widest, "limit": limit},
        "short_answers": {"value": len(short), "limit": 0},
    }
    if control:
        checks["program_logit_gap"] = {
            "value": max((float(g.max()) for g in program), default=None),
            "limit": limit}
    out = {
        "correct": bool(served > 0 and widest <= limit and not short),
        "attempted": len(followed),
        "failed": len(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def check_lines(result: dict) -> list[str]:
    """The numbers compared, each beside its limit."""
    return [f"check {name} {c['value']} limit {c['limit']}"
            for name, c in result["checks"].items()]
