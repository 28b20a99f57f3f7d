"""The benchmark harness: builds the system under test from a cell's data
files, warms it up, drives one measured window of traffic through the
program's serving loop, reduces what it saw to metrics, and checks what the
timed path served against the plain reference.

The program is driven only through its public serving API: the model's
engine behind an ``EngineRegistry``, a ``RequestQueue``, a ``Batcher`` and
a stepwise ``ServingLoop``.  The load generator, the timing, the reduction
and the reference are the benchmark's own.

Everything that depends on the model lives in one module per architecture,
``models/<arch>.py`` under the cell's benchmark directory, chosen by the
configuration file's ``arch`` key and loaded by path.  It provides:

    program_arch(cfg)       the program's architecture object, from the
                            configuration's sizes
    program_layout(arch)    the weight shapes the program reads, a tree of
                            shape tuples (``check_layout`` compares it)
    leaf_shapes(cfg)        the weight tree ``weights.make_weights`` fills
    make_engine(params, arch, coeffs, spec, placement)
                            the program's ``SamplingEngine`` for the model
    draw_condition(rng, cfg)  a request's condition, a small JSON-able
                            value drawn from the run's generator
    warm_condition(i, cfg)  the condition of the i-th warm-up request
    request_kwargs(cond)    ``SampleRequest`` keyword arguments for it
    forward(params, x, t, cond, *, dtype)
                            the plain reference denoiser: eps for (R, ...)
                            rows at (R,) timesteps under one condition
    forward_flops(cfg)      FLOPs of one served row's evaluation

So a configuration of another denoiser is added as new files: its
configuration, its model module, its traffic and limits files and its
metric readers.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import statistics
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
SRC = ROOT / "src"


# ---------------------------------------------------------------------------
# the cell's data files
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench: Path                      # the benchmark directory it came from


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Everything one workload names, found by name: its configuration
    file, ``traffic/<traffic>.json``, ``limits/<workload>.json``, and the
    metrics that apply to it."""
    spec = _read_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(by_name)}")
    wl = by_name[name]
    conf = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in spec["per_layer"] if name in m["workloads"]]
    bench = root / spec["paths"][0]
    return Cell(name=name, chips=int(wl["chips"]),
                config=_read_json(root / conf["file"]),
                traffic=_read_json(bench / "traffic" / f"{wl['traffic']}.json"),
                limits=_read_json(bench / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer, bench=bench)


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str, bench: Path = BENCH_DIR):
    """``metrics/<name>.py``'s ``read(ctx)``."""
    return _load(bench / "metrics" / f"{name}.py", f"metric_{name}").read


_MODELS: Dict[Path, object] = {}


def load_model(arch: str, bench: Path = BENCH_DIR):
    """The model module ``models/<arch>.py``, loaded once per process (its
    ``forward`` is a static argument of the reference's jitted step, so it
    keeps one identity)."""
    path = (bench / "models" / f"{arch}.py").resolve()
    if path not in _MODELS:
        if not path.is_file():
            raise SystemExit(f"no model module for arch {arch!r} at {path}")
        _MODELS[path] = _load(path, f"model_{arch}")
    return _MODELS[path]


def peaks_for(kind: str) -> dict:
    table = _read_json(BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# JAX set-up
# ---------------------------------------------------------------------------


def configure_jax() -> None:
    """Persistent compilation cache at a fixed path inside the checkout
    (``JAX_COMPILATION_CACHE_DIR`` wins when set), every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_devices(chips: int):
    """The accelerator the cell asks for, or SystemExit (no result)."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found {devices[0].platform} "
                         f"({devices[0].device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"needs {chips} chip(s); JAX found {len(devices)}")
    return devices


class CompileMeter:
    """Counts of JAX traces, backend compiles and persistent-cache hits,
    read from ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.counts = {"traces": 0, "compiles": 0, "cache_hits": 0}
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.counts["traces"] += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.counts["compiles"] += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1

    def mark(self) -> dict:
        return dict(self.counts)

    def since(self, mark: dict) -> dict:
        return {k: self.counts[k] - mark[k] for k in self.counts}


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def check_layout(params, model, arch) -> None:
    """The benchmark's weight tree has the shapes the program reads."""
    import jax
    want = model.program_layout(arch)
    have = jax.tree.map(lambda a: tuple(a.shape), params)
    if want != have:
        raise SystemExit(f"weight layout differs from the program's: "
                         f"{have} != {want}")


@dataclasses.dataclass
class System:
    """Weights plus one warmed engine of the program."""
    cfg: dict
    traffic: dict
    model: object                    # the configuration's model module
    params: object
    engine: object
    key: object
    slots: int
    sample_shape: tuple


def sampler_spec(traffic: dict):
    from repro.sampling import get_sampler
    solver = dict(traffic["solver"])
    return get_sampler(solver.pop("name"), **solver)


def placement_for(traffic: dict, chips: int):
    """The engine's placement: the traffic file's optional ``mesh`` block
    (``{"name": <registered mesh>, "data": n, "time": n, "model": n}``, a
    size left out keeping the mesh's own) through ``serve.make_placement``,
    spanning exactly the cell's chips; without one, the host placement."""
    from repro.launch import serve
    mesh = traffic.get("mesh")
    if mesh is None:
        return serve.make_placement("none")
    placement = serve.make_placement(
        mesh["name"], data_parallel=mesh.get("data", 0),
        model_parallel=mesh.get("model", 0),
        time_parallel=mesh.get("time", 0))
    if placement.mesh.devices.size != chips:
        raise SystemExit(f"mesh {mesh} spans {placement.mesh.devices.size} "
                         f"devices; the cell asks for {chips} chip(s)")
    return placement


def build_system(cell: Cell, seed: int) -> System:
    import jax
    from repro.core import ddim_coeffs
    from repro.serving import EngineKey
    import weights

    cfg, traffic = cell.config, cell.traffic
    if traffic["sampler"] != "ddim":
        raise SystemExit(f"sampler {traffic['sampler']!r}: the reference "
                         f"implements DDIM only")
    model = load_model(cfg["arch"], cell.bench)
    arch = model.program_arch(cfg)
    params = weights.make_weights(model.leaf_shapes(cfg), cfg["init"],
                                  weights.seed_key(seed))
    jax.block_until_ready(params)
    check_layout(params, model, arch)
    engine = model.make_engine(params, arch, ddim_coeffs(traffic["T"]),
                               sampler_spec(traffic),
                               placement_for(traffic, cell.chips))
    key = EngineKey(cfg["name"], traffic["T"], traffic["solver"]["name"])
    slots = engine.placement.round_batch(traffic["slots"])
    return System(cfg=cfg, traffic=traffic, model=model, params=params,
                  engine=engine, key=key, slots=slots,
                  sample_shape=tuple(engine.sample_shape))


def warm_up(system: System) -> None:
    """Compile and run every program and shape the window drives: the
    stepwise open/init/merge/step/gather programs, refills of 1..slots
    lanes, and harvests of 1..slots lanes at once."""
    from repro.sampling import SampleRequest
    engine, slots = system.engine, system.slots
    chunk = system.traffic["chunk_iters"]
    seq = engine.spec.is_sequential

    def cond(i):
        return system.model.request_kwargs(
            system.model.warm_condition(i, system.cfg))

    for k in range(1, slots + 1):
        bank = engine.stepwise_open(slots, chunk_iters=chunk)
        # a ParaTAA lane with max_iters=0 retires at birth, so the harvest
        # of k lanes at once costs no step
        reqs = [SampleRequest(seed=i, **cond(i)) if seq else
                SampleRequest(seed=i, max_iters=0, **cond(i))
                for i in range(k)]
        engine.stepwise_refill(bank, list(range(k)), reqs)
        if not seq:
            engine.stepwise_harvest(bank)
    # one lane through the steady round: step, piggybacked poll, harvest
    bank = engine.stepwise_open(slots, chunk_iters=chunk)
    req = SampleRequest(seed=1, **cond(1)) if seq else \
        SampleRequest(seed=1, quality_steps=chunk, **cond(1))
    engine.stepwise_refill(bank, [0], [req])
    for _ in range(2 * system.traffic["T"] + 2):    # a broken step never
        engine.stepwise_step(bank)                  # retires the lane
        engine.stepwise_harvest(bank)
        if not bank.occupied:
            break
    if seq:
        # a sequential harvest slices the first n gathered rows: warm the
        # slices of 2..slots rows on an array of the gathered shape
        for n in range(2, slots + 1):
            bank.state.x[:n].block_until_ready()
    engine.reset_stats()


# ---------------------------------------------------------------------------
# one measured window
# ---------------------------------------------------------------------------


def marked_loop(*args, **kwargs):
    """A ``ServingLoop`` that can report its banks mid-run.

    ``bank_reports()`` is single-consumer: it shares the round's poll with
    the pump, so only the loop's own thread may call it while the loop
    runs.  ``mark()`` asks that thread for the reports at its next round
    boundary and waits for them."""
    from repro.serving import ServingLoop

    class MarkedLoop(ServingLoop):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self._mark_wanted = threading.Event()
            self._mark_taken = threading.Event()
            self.marked: Optional[dict] = None

        def pump(self, **kw):
            n = super().pump(**kw)
            if self._mark_wanted.is_set() and not self._mark_taken.is_set():
                try:
                    self.marked = self.bank_reports()
                except Exception:  # noqa: BLE001 — no reading; the
                    self.marked = None      # requests' own failures count
                self._mark_taken.set()
            return n

        def mark(self, timeout: float) -> Optional[dict]:
            self._mark_wanted.set()
            self._mark_taken.wait(timeout)
            return self.marked

    return MarkedLoop(*args, **kwargs)


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    sent: list
    lateness: List[float]
    chunks: int
    bank_reports: Optional[dict]     # loop.bank_reports() as the window closed
    queue_wait_p50_s: Optional[float]
    compiles: dict
    trace: object = None
    end: float = 0.0                 # when the drain ended


def run_window(system: System, seed: int, seconds: float, *,
               trace_dir: Optional[str] = None, meter=None,
               rate_per_s: Optional[float] = None) -> Window:
    """Serve one window of the cell's traffic and drain it.  The window
    opens when the generator starts; requests still open ``drain_s`` after
    it closes are failed by the loop's shutdown."""
    import jax
    from repro.obs import Observability
    from repro.sampling import SampleRequest
    from repro.serving import (Batcher, BatchingPolicy, EngineRegistry,
                               RequestQueue)
    import loadgen
    import trace_reduce

    traffic = system.traffic
    obs = Observability()
    registry = EngineRegistry(lambda key: system.engine)
    queue = RequestQueue(obs=obs)
    batcher = Batcher(BatchingPolicy(
        max_batch=traffic["slots"],
        max_wait_s=traffic["max_wait_ms"] / 1e3),
        metrics=obs.metrics)
    loop = marked_loop(registry, queue, batcher,
                       chunk_iters=traffic["chunk_iters"], obs=obs)
    key, model = system.key, system.model

    def draw_condition(rng):
        return model.draw_condition(rng, system.cfg)

    def submit(cond, noise_seed, due):
        return queue.submit(SampleRequest(seed=noise_seed, arrival_time=due,
                                          **model.request_kwargs(cond)), key)

    drain_s = float(traffic["drain_s"])
    if traffic["arrivals"] == "poisson":
        gen = loadgen.OpenLoop(loadgen.poisson_schedule(
            traffic["arrival_seed"], seed,
            rate_per_s or traffic["rate_per_s"], seconds, draw_condition),
            submit, clock=queue.clock)
    elif traffic["arrivals"] == "closed":
        gen = loadgen.ClosedLoop(seed, traffic["clients"],
                                 traffic["client_stagger_s"],
                                 draw_condition,
                                 submit, result_timeout=seconds + drain_s,
                                 clock=queue.clock)
    else:
        raise SystemExit(f"unknown arrivals {traffic['arrivals']!r}")

    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    mark = meter.mark() if meter else None
    loop.start()
    t0 = queue.clock()
    gen.start(t0)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_EVENT):
        time.sleep(max(t0 + seconds - queue.clock(), 0.0))
    t1 = queue.clock()
    chunks = loop.stats["chunks"]
    wait = obs.metrics.histogram("loop.queue_wait_s").merged()
    compiles = meter.since(mark) if meter else {}
    # the loop's banks are new in this window: their counters started at 0
    reports = loop.mark(timeout=drain_s)
    if trace_dir is not None:
        jax.profiler.stop_trace()

    gen.stop(timeout=drain_s)
    deadline = t1 + drain_s
    for s in gen.sent:
        if s.ticket is None:
            continue
        try:
            s.ticket.result(timeout=max(deadline - queue.clock(), 0.0))
        except Exception:  # noqa: BLE001 — counted as failed below
            pass
    loop.stop(drain=False)      # fails whatever is still open
    window = Window(t0=t0, t1=t1, sent=list(gen.sent),
                    lateness=gen.lateness(), chunks=chunks,
                    bank_reports=reports,
                    queue_wait_p50_s=None if wait is None else wait["p50"],
                    compiles=compiles, end=queue.clock())
    if trace_dir is not None:
        window.trace = trace_reduce.reduce(trace_reduce.load(trace_dir))
    return window


def outcome(sent) -> list:
    """(sent request, its result or None) for each sent request."""
    out = []
    for s in sent:
        t = s.ticket
        res = None
        if t is not None and t.done():
            try:
                res = t.result(timeout=0)
            except Exception:  # noqa: BLE001 — a failed request
                res = None
        out.append((s, res))
    return out


# ---------------------------------------------------------------------------
# end-to-end and per-layer numbers
# ---------------------------------------------------------------------------


def end_to_end(system: System, window: Window, setup_s: float) -> dict:
    """All end-to-end numbers this window can give, by metric name."""
    served = outcome(window.sent)
    seconds = window.t1 - window.t0
    out = {"setup_s": setup_s}
    if system.traffic["arrivals"] == "poisson":
        # every request due in the window; an unserved one waited at least
        # until the drain ended
        lat = [(s.ticket.completed_time if res is not None else window.end)
               - s.due for s, res in served]
        out["latency_p50_s"] = float(np.percentile(lat, 50))
        out["latency_p90_s"] = float(np.percentile(lat, 90))
    done_in = sum(1 for s, res in served if res is not None
                  and window.t0 <= s.ticket.completed_time <= window.t1)
    out["images_per_s"] = done_in / seconds
    return out


def attempted_failed(system: System, window: Window) -> tuple:
    served = outcome(window.sent)
    if system.traffic["arrivals"] == "poisson":
        mine = served
    else:                               # sent inside the window
        mine = [(s, r) for s, r in served if s.due <= window.t1]
    return len(mine), sum(1 for _, r in mine if r is None)


def layer_context(system: System, window: Window, chips: int,
                  peaks: dict) -> dict:
    """What the per-layer readers read."""
    served = outcome(window.sent)
    in_window = [res for s, res in served if res is not None
                 and window.t0 <= s.ticket.completed_time <= window.t1]
    spec = system.engine.spec
    T = system.traffic["T"]
    D = int(np.prod(system.sample_shape))
    return {
        "window_s": window.t1 - window.t0,
        "chips": chips,
        "peaks": peaks,
        "chunks": window.chunks,
        "chunk_iters": system.traffic["chunk_iters"],
        "slots": system.slots,
        "rows_per_lane_iter": system.engine.window,
        "flops_per_row": system.model.forward_flops(system.cfg),
        "completed_iters": [int(r.iters) for r in in_window],
        "bank_reports": window.bank_reports,
        "sequential": spec.is_sequential,
        "queue_wait_p50_s": window.queue_wait_p50_s,
        "trace": window.trace,
        "taa": None if spec.is_sequential else {
            "T": T, "D": D, "m": spec.history_m, "lanes": system.slots},
    }


# ---------------------------------------------------------------------------
# correct: the served trajectories against the plain reference
# ---------------------------------------------------------------------------


def pick_checked(served, n: int, seed: int) -> list:
    """A sample drawn from the seed of the requests served, with the one
    that took the most solver iterations (the longest) always in it."""
    done = [(s, r) for s, r in served if r is not None]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: done[i][1].iters)
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([int(seed), 3])
    take = rng.choice(rest, size=min(n - 1, len(rest)), replace=False) \
        if rest and n > 1 else []
    return [done[longest]] + [done[int(i)] for i in take]


def correctness(system: System, window: Window, seed: int,
                dtype=None) -> Dict[str, float]:
    """The compared numbers.  ``step_gap``: over the checked rows, the
    widest excess of a served x_{t-1} over the float32 reference's DDIM
    step from the served x_t, beyond the solver tolerance the traffic file
    states, relative to |b eps_ref| (``dtype`` set: the control, the
    reference's step in that precision in the served row's place);
    ``noise_row``: the largest |x_T - xi_T| over the checked requests, the
    request's own initial noise redrawn from its seed; ``unserved``: the
    requests that never completed."""
    import jax
    import jax.numpy as jnp
    import reference

    check = system.traffic["check"]
    served = outcome(window.sent)
    picked = pick_checked(served, check["requests"], seed)
    sched = reference.ddim_schedule(system.traffic["T"])
    T = system.traffic["T"]
    shape = (T + 1,) + system.sample_shape
    tau = system.traffic["solver"].get("tau")
    thresh2 = None if tau is None else reference.stopping_thresholds(
        sched, tau, int(np.prod(system.sample_shape)))
    gap, noise = 0.0, 0.0
    for s, res in picked:
        traj = np.asarray(res.trajectory, np.float32)
        xi = np.asarray(jax.random.normal(jax.random.PRNGKey(s.noise_seed),
                                          shape, jnp.float32))
        noise = max(noise, float(np.max(np.abs(traj[T] - xi[T]))))
        g2, b2 = reference.step_readings(
            system.model.forward, system.params, traj, s.cond, sched,
            block=check["block"],
            dtype=jnp.float32 if dtype is None else dtype)
        gap = max(gap, reference.step_gap(g2, b2, thresh2))
    _, failed = attempted_failed(system, window)
    return {"step_gap": gap, "noise_row": noise, "unserved": float(failed),
            "checked": float(len(picked))}


def judge(numbers: Dict[str, float], limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) over the limited numbers."""
    checks = {name: {"value": numbers[name], "limit": limit}
              for name, limit in limits.items()}
    ok = numbers.get("checked", 0) > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


# ---------------------------------------------------------------------------
# one run, start to end
# ---------------------------------------------------------------------------


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        process_start: float, *, devices=None, out=print) -> dict:
    """Set up, serve one window, reduce, check.  Returns the result line.
    ``devices`` given skips the look for a chip and the JAX set-up (tests
    drive a run on the CPU that way)."""
    sys.path.insert(0, str(SRC))
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"the system under test is not at {SRC}")
    if devices is None:
        configure_jax()
        devices = find_devices(cell.chips)
    dev = devices[0]
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    meter = CompileMeter()
    system = build_system(cell, seed)
    warm_up(system)
    out(f"set-up: {meter.counts['compiles']} backend compile(s) "
        f"({meter.compile_s:.1f}s), {meter.counts['cache_hits']} "
        f"persistent-cache hit(s), {meter.counts['traces']} trace(s)")

    tmp = tempfile.TemporaryDirectory() if trace else None
    window = run_window(system, seed, seconds, meter=meter,
                        trace_dir=tmp.name if tmp else None)
    setup_s = window.t0 - process_start
    attempted, failed = attempted_failed(system, window)
    lat = window.lateness
    out(f"window: {window.t1 - window.t0:.3f}s, {attempted} request(s) "
        f"attempted, {failed} failed; compiles inside the window: "
        f"{window.compiles}")
    out(f"traffic: {cell.traffic['arrivals']}, "
        + (f"rate {cell.traffic['rate_per_s']}/s" if
           cell.traffic["arrivals"] == "poisson" else
           f"{cell.traffic['clients']} clients")
        + f", {system.slots} slots, chunk_iters "
        f"{cell.traffic['chunk_iters']}, solver {cell.traffic['solver']}")
    if lat:
        out(f"generator lateness: p50 {statistics.median(lat) * 1e3:.3f}ms,"
            f" max {max(lat) * 1e3:.3f}ms over {len(lat)} request(s)")

    if trace:
        ctx = layer_context(system, window, cell.chips, peaks)
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"], cell.bench)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = end_to_end(system, window, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    red = window.trace
    if red is not None:
        device.update(busy_s=red.busy_s, window_s=red.window_s)
    if tmp is not None:
        tmp.cleanup()

    # the program's state goes before the reference runs
    system.engine = None
    gc.collect()
    numbers = correctness(system, window, seed)
    ok, checks = judge(numbers, cell.limits)
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if red is not None:
        import trace_reduce
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(red),
                               "idle_gaps": [list(g) for g in red.gaps]}
    result["checks"] = checks
    return result
