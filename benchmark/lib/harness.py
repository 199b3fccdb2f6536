"""The benchmark harness: one cell of BENCHMARK.json, run on the chips.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name BENCHMARK.json gives it:

    benchmark/configs/<config>.json   sizes, source, guarantees, limits
    benchmark/configs/<config>.py     build(cfg, traffic, control), sample,
                                      reference, progress, horizon_us
    benchmark/traffic/<traffic>.json  the mix: kind ("sweep" | "triage")
                                      and its parameters
    benchmark/metrics/<metric>.py     read(ctx) -> number | None

A run: fixed compile cache, the cell's chips, the workload and one sim,
a warm-up on seeds outside the window (counted in setup_s), the window
(calls that begin inside --seconds, each on fresh seeds), then the
comparison with the configuration's plain reference, and one result
line. `--trace 1` profiles the first calls of the window and reports the
per-layer metrics instead of the end-to-end ones.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import seeds as seedlib

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
OUT_DIR = os.path.join(ROOT, ".bench_out")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------- discovery


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # configs/<config>.json
    factory: Any  # configs/<config>.py
    traffic: dict  # traffic/<traffic>.json
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[dict]

    def metric_reader(self, name: str):
        return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                           "bench_metric_" + name.replace(".", "_"))


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_dir: str = BENCH_DIR,
              traffic_override: Optional[dict] = None) -> Cell:
    """The cell `name` of BENCHMARK.json with its files."""
    with open(os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg_path = os.path.join(bench_dir, "configs", w["config"])
    with open(cfg_path + ".json") as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")) as f:
        traffic = {**json.load(f), **(traffic_override or {})}
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in moved and _reports(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                factory=load_module(cfg_path + ".py",
                                    "bench_config_" + w["config"]),
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


# ------------------------------------------------------------------ process


def prepare_env() -> None:
    """Before JAX is imported: the checkout's own compile cache (a fixed
    path, so the second run of a cell finds every program), and no child
    process (the host runtime's native core builds itself in one)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("MADSIM_NO_NATIVE_BUILD", "1")
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")


def configure_jax() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # every program, down to the small shrink and gather programs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips(n: int, require_tpu: bool = True) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0]} "
                     f"({devs[0].platform})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def mesh_for(devices) -> Any:
    """run_batch's default mesh ("auto": every visible device) where the
    host holds exactly the cell's chips, else a lane mesh over those."""
    import jax

    if len(jax.devices()) == len(devices):
        return "auto"
    if len(devices) == 1:
        return None
    return jax.sharding.Mesh(np.array(devices), ("seeds",))


class CompileCounter:
    """Counts JAX traces and backend compiles, so the window can show it
    compiled nothing."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self) -> None:
        import jax.monitoring

        self.counts = {"traces": 0, "compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)


def window(seconds: float, call: Callable[[int], dict],
           clock: Callable[[], float] = time.perf_counter):
    """Run `call(k)` for k = 0, 1, ... while a call can begin inside
    `seconds`; the window ends with the last whole call. Returns (elapsed
    seconds, the calls' records)."""
    t0 = clock()
    records = []
    while clock() - t0 < seconds:
        records.append(call(len(records)))
    return clock() - t0, records


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -------------------------------------------------------------------- sweep


def sweep_record(r, seeds: np.ndarray, max_steps: int,
                 chunk_sizes: List[int]) -> dict:
    """What one run_batch call answered, for the window's counts.

    `failed` counts seeds without an honest verdict: a lane that did not
    finish (no step, or max_steps reached) or that overflowed the message
    pool. Overflow is exact per lane for the last chunk (the final state);
    for earlier chunks only the summed drop count is returned, so at most
    that many of their lanes are counted. `chunk_sizes` are the lanes of
    each chunk the call ran, in order (the sampling hook saw them)."""
    steps = np.asarray(r.retired_step, np.int64)
    rows = min(len(r.violated), len(steps))
    unfinished = int(((steps <= 0) | (steps >= max_steps)).sum())
    last_over = np.asarray(r.state.overflow) > 0
    last_msgs = int(np.asarray(r.state.overflow, np.int64).sum())
    earlier = max(int(r.summary.get("total_overflow", 0)) - last_msgs, 0)
    over_lanes = int(last_over.sum()) + min(earlier, max(rows - last_over.size, 0))
    ends = np.cumsum(chunk_sizes)
    return {
        "seeds": int(seeds.size), "rows": rows,
        "violations": int(r.violations)
        + int(r.summary.get("lane_check_violations", 0)),
        "failed": unfinished + over_lanes, "unfinished": unfinished,
        "overflow_msgs": int(r.summary.get("total_overflow", 0)),
        "events": int(r.summary.get("total_events", 0)),
        "occupancy": float(r.summary["occupancy"]),
        # the engine loop's iterations: each chunk runs to its longest lane
        "loop_steps": int(sum(steps[e - n:e].max(initial=0)
                              for n, e in zip(chunk_sizes, ends))),
    }


@dataclasses.dataclass
class Run:
    """What a cell's run measured, for the result line and the readers."""

    cell: Cell
    elapsed: float = 0.0
    records: List[dict] = dataclasses.field(default_factory=list)
    checks: List[tuple] = dataclasses.field(default_factory=list)
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Any = None  # trace.Summary of the profiled stretch
    spans: list = dataclasses.field(default_factory=list)
    oracle_s: List[float] = dataclasses.field(default_factory=list)
    breakdown: Optional[dict] = None
    memory_peak: int = 0

    def total(self, key: str) -> int:
        return sum(r[key] for r in self.records)


class Profiler:
    """Profiles the window's first `calls` calls (trace runs only), for at
    most `max_s` seconds: a timer ends a stretch that would hold more device
    events than the profiler stops in time. Host markers open and close the
    stretch, and the opening one's perf_counter aligns the program's spans
    to the trace."""

    START, END = "bench.traced.start", "bench.traced.end"

    def __init__(self, on: bool, calls: int, max_s: Optional[float],
                 trace_dir: str) -> None:
        self.on, self.calls, self.max_s, self.dir = on, calls, max_s, trace_dir
        self.active = False
        self.lock = threading.Lock()
        self.timer: Optional[threading.Timer] = None
        self.pc = 0.0  # perf_counter at the opening marker

    def before(self, k: int) -> None:
        import jax

        if not (self.on and k == 0):
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        # host spans come from annotations; device ops all stay
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(self.START):
            self.pc = time.perf_counter()
        self.active = True
        if self.max_s:
            self.timer = threading.Timer(self.max_s, self.stop)
            self.timer.start()

    def after(self, k: int) -> None:
        if k + 1 >= self.calls:
            self.stop()

    def stop(self) -> None:
        """Ends the profiled stretch (also when the window held fewer
        calls than asked); called by the timer or by the window."""
        import jax

        with self.lock:
            if not self.active:
                return
            with jax.profiler.TraceAnnotation(self.END):
                pass
            jax.profiler.stop_trace()
            self.active = False
        if self.timer is not None and threading.current_thread() \
                is not self.timer:
            self.timer.cancel()


def measure(run: Run, one: Callable[[int], dict], seconds: float,
            trace: bool, devices: list, t_start: float,
            counter: CompileCounter) -> Optional[Profiler]:
    """Set-up ends here (after the caller's warm-up); then the window, with
    the profiler and telemetry on in a traced run. Fills `run`."""
    from madsim_tpu import telemetry

    run.notes["setup_s"] = time.perf_counter() - t_start
    tr = run.cell.traffic
    prof = Profiler(trace, int(tr.get("trace_calls", 1)),
                    tr.get("trace_max_s"),
                    os.path.join(OUT_DIR, run.cell.name, "trace"))
    if trace:
        telemetry.enable()
        run.notes["pc_telemetry"] = time.perf_counter()
    before = counter.snapshot()

    def call(k: int) -> dict:
        prof.before(k)
        t = time.perf_counter()
        rec = one(k)
        rec["call_s"] = time.perf_counter() - t
        prof.after(k)
        return rec

    run.elapsed, run.records = window(seconds, call)
    prof.stop()
    after = counter.snapshot()
    if trace:
        run.spans = telemetry.spans()
        telemetry.disable()
    run.notes.update(traces_in_window=after["traces"] - before["traces"],
                     compiles_in_window=after["compiles"] - before["compiles"])
    run.memory_peak = memory_peak(devices)
    return prof


def run_sweep(cell: Cell, seed: int, seconds: float, trace: bool,
              devices: list, control: Optional[str], t_start: float,
              counter: CompileCounter) -> Run:
    import jax

    from madsim_tpu.tpu import BatchedSim
    from madsim_tpu.tpu.batch import run_batch

    tr = cell.traffic
    wl = cell.factory.build(cell.config, tr, control)
    run = Run(cell=cell)
    per_chunk = int(tr["sample_lanes_per_chunk"])
    chunks: List[tuple] = []  # this call's (size, sampled lanes, arrays)
    call_k = [0]

    def sample_chunk(state) -> None:
        """The reference's sample: lanes of each chunk's final state, drawn
        from the run seed, read where run_batch hands the chunk to its
        lane_check hook."""
        size = int(state.done.shape[0])
        lanes = np.sort(seedlib.rng(seed, 1 + 4096 * (call_k[0] + 1)
                                    + len(chunks)).choice(
            size, size=min(per_chunk, size), replace=False))
        chunks.append((size, lanes, cell.factory.sample(state, lanes)))

    inner = wl.lane_check

    def hook(state, lanes):
        out = {}
        if inner is not None:
            with jax.profiler.TraceAnnotation("bench.lane_check"):
                t = time.perf_counter()
                out = inner(state, lanes)
                run.oracle_s.append(time.perf_counter() - t)
        sample_chunk(state)
        return out

    wl = dataclasses.replace(wl, lane_check=hook)
    sim = BatchedSim(wl.spec, wl.config)
    mesh = mesh_for(devices)
    size = int(tr["seeds_per_call"])
    samples: List[tuple] = []  # (arrays, seeds, rows the program returned)

    def one(k: int, keep: bool) -> dict:
        seeds = seedlib.block(seed, k, size)
        call_k[0] = k
        chunks.clear()
        with jax.profiler.TraceAnnotation("bench.call"):
            r = run_batch(seeds, wl, sim=sim, mesh=mesh)
        rec = sweep_record(r, seeds, wl.max_steps, [c[0] for c in chunks])
        off = 0
        for n, lanes, picked in chunks:
            rows = off + lanes
            off += n
            if keep:
                samples.append((picked, seeds[rows], {
                    "violated": np.asarray(r.violated)[rows],
                    "violation_step": np.asarray(r.violation_step)[rows],
                    "steps": np.asarray(r.retired_step)[rows]}))
        return rec

    one(-1, keep=False)  # warm-up: every program of the window compiles
    run.oracle_s.clear()
    prof = measure(run, lambda k: one(k, keep=True), seconds, trace, devices,
                   t_start, counter)
    # ---- the comparison with the plain reference, after the window
    t = time.perf_counter()
    horizon = cell.factory.horizon_us(cell.config, tr)
    broken: Dict[str, int] = {}
    bad_lanes = checked = disagree = 0
    progress: List[int] = []
    for picked, lane_seeds, rows in samples:
        # the program's per-seed rows must be these lanes' own answers
        disagree += int(sum((rows[f] != picked[f]) for f in rows).astype(
            bool).sum())
        progress.extend(int(p) for p in cell.factory.progress(picked))
        for b in cell.factory.reference(picked, lane_seeds, horizon):
            checked += 1
            bad_lanes += bool(b)
            for g in b:
                broken[g] = broken.get(g, 0) + 1
    mean_progress = float(np.mean(progress)) if progress else 0.0
    run.notes.update(reference_s=time.perf_counter() - t,
                     reference_broken=broken,
                     progress_per_chunk=[
                         float(np.mean(cell.factory.progress(p)))
                         for p, _, _ in samples])
    n = run.total("seeds")
    run.checks = [
        ("seeds_missing", n - run.total("rows"), 0, "at_most"),
        ("violations", run.total("violations"), 0, "at_most"),
        ("failed_seeds", run.total("failed"), 0, "at_most"),
        ("rows_disagree", disagree, 0, "at_most"),
        ("reference_broken_lanes", bad_lanes, 0, "at_most"),
        ("reference_lanes", checked, len(run.records), "at_least"),
        ("reference_progress", round(mean_progress, 3),
         cell.config["checks"]["progress_per_lane"], "at_least"),
    ]
    if trace:
        reduce_trace(run, prof)
    return run


# ------------------------------------------------------------------- triage


def run_triage(cell: Cell, seed: int, seconds: float, trace: bool,
               devices: list, control: Optional[str], t_start: float,
               counter: CompileCounter) -> Run:
    import jax

    from madsim_tpu.repro import ReplayError, replay_device
    from madsim_tpu.tpu import BatchedSim
    from madsim_tpu.tpu.batch import run_batch
    from madsim_tpu.triage import ReproBundle

    tr = cell.traffic
    wl = cell.factory.build(cell.config, tr, control)
    sim = BatchedSim(wl.spec, wl.config)
    tsim = BatchedSim(wl.spec, wl.config, triage=True)
    mesh = mesh_for(devices)
    size = int(tr["seeds_per_cycle"])
    out = os.path.join(OUT_DIR, cell.name, "bundles")
    shutil.rmtree(out, ignore_errors=True)
    run = Run(cell=cell)

    def one(k: int) -> dict:
        seeds = seedlib.block(seed, k, size)
        d = os.path.join(out, f"cycle{k + 1}")
        with jax.profiler.TraceAnnotation("bench.cycle"), \
                warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            r = run_batch(
                seeds, wl, sim=sim, mesh=mesh, shrink_on_violation=True,
                max_traces=int(tr["max_traces"]),
                shrink_kwargs={"out_dir": d, "sim": tsim,
                               "trace_tail": int(tr["trace_tail"])},
            )
        path = r.bundle_path
        ok = bool(r.violations and path and os.path.exists(path)
                  and r.bundle.seed in set(r.violating_seeds))
        return {"cycles": 1, "violations": int(r.violations),
                "bundle": path if ok else None, "failed": int(not ok),
                "overflow_msgs": int(r.summary.get("total_overflow", 0)),
                "warnings": [str(w.message)[:200] for w in caught]}

    one(-1)  # warm-up: one whole cycle
    prof = measure(run, one, seconds, trace, devices, t_start, counter)
    # ---- replay a sample of the window's bundles: the program's replay
    # must fire at the recorded step (determinism), and the plain reference
    # must find a guarantee broken in the lane's state at that step
    t = time.perf_counter()
    bundles = [r["bundle"] for r in run.records if r["bundle"]]
    pick = seedlib.rng(seed, 0).permutation(len(bundles))[
        :int(tr["replay_bundles"])]
    mismatched = unbroken = 0
    quiet = lambda _msg: None  # noqa: E731
    rsims: Dict[str, Any] = {}
    for i in sorted(pick):
        b = ReproBundle.load(bundles[i])
        try:
            rep = replay_device(b, spec=wl.spec, repeats=2, out=quiet)
        except ReplayError:  # fired elsewhere, or not at all
            mismatched += 1
            continue
        mismatched += not (rep["violated"] and rep["step"] == b.violation_step)
        cfg = b.config()
        rsim = rsims.setdefault(b.config_hash, BatchedSim(wl.spec, cfg,
                                                          triage=True))
        st = rsim.run([b.seed], max_steps=b.max_steps, ctl=b.ctl(1))
        picked = cell.factory.sample(st, [0])
        broken = cell.factory.reference(
            picked, np.asarray([b.seed], np.uint32))[0]
        at_step = int(picked["violation_step"][0]) == b.violation_step
        unbroken += not (at_step and set(broken) & set(cell.factory.SAFETY))
    run.notes.update(reference_s=time.perf_counter() - t)
    run.checks = [
        ("cycles_without_bundle", run.total("failed"), 0, "at_most"),
        ("replays_off_step", mismatched, 0, "at_most"),
        ("bundles_reference_sound", unbroken, 0, "at_most"),
        ("bundles_replayed", len(pick), 1, "at_least"),
    ]
    if trace:
        reduce_trace(run, prof)
    return run


KINDS = {"sweep": run_sweep, "triage": run_triage}


# ----------------------------------------------------------------- results


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def reduce_trace(run: Run, prof: Profiler) -> None:
    """The profiled stretch to a trace.Summary, plus the breakdown: top
    device ops, and idle time by the host span around it. The program's
    telemetry spans (perf_counter) are put on the trace clock by the
    offset between the opening marker's perf_counter and its time in the
    trace."""
    from . import trace as tracelib

    events = tracelib.load(tracelib.find_xplane(prof.dir))
    lo, hi = tracelib.window_of(events, prof.START, prof.END)
    run.trace = tracelib.reduce(events, lo, hi)
    offset = lo - prof.pc * 1e9
    t0 = run.notes["pc_telemetry"]
    host = [(n, s, d) for n, s, d in events["host"]
            if n.startswith("bench.") and d > 0]
    for sp in run.spans:
        site = sp.labels.get("site", "")
        host.append((f"{sp.name}[{site}]" if site else sp.name,
                     (t0 + sp.t0_s) * 1e9 + offset, sp.dur_s * 1e9))
    run.breakdown = {
        "device_ops": [[n, s] for n, s in run.trace.top_ops(10)],
        "idle_gaps": [[n, s] for n, s in tracelib.label_idle(run.trace, host)],
    }
    run.notes["traced_window_s"] = (hi - lo) / 1e9


def passes(value, limit, sense: str) -> bool:
    return value <= limit if sense == "at_most" else value >= limit


def result(run: Run, devices: list, trace: bool) -> dict:
    cell = run.cell
    metrics: Dict[str, dict] = {}
    if trace:
        for m in cell.per_layer:
            v = cell.metric_reader(m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {
            "setup_s": run.notes["setup_s"],
            "seeds_per_s": run.total("seeds") / run.elapsed
            if cell.traffic["kind"] == "sweep" else None,
            "bundle_s": run.elapsed / max(
                sum(1 for r in run.records if r.get("bundle")), 1)
            if cell.traffic["kind"] == "triage" else None,
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": run.memory_peak}
    if trace:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_ns / 1e9
    out = {
        "correct": all(passes(v, lim, s) for _, v, lim, s in run.checks),
        "attempted": run.total("seeds" if cell.traffic["kind"] == "sweep"
                               else "cycles"),
        "failed": run.total("failed"),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        out["breakdown"] = run.breakdown
    out["checks"] = {n: {"value": v, "limit": lim, "must_be": s}
                     for n, v, lim, s in run.checks}
    return out


def report(run: Run, res: dict) -> None:
    """The earlier lines: what the window did, then each number compared
    beside its limit, last on standard error."""
    info = {k: v for k, v in run.notes.items() if k != "pc_telemetry"}
    info.update(window_s=run.elapsed, calls=len(run.records),
                call_s=[r.get("call_s") for r in run.records],
                overflow_msgs=run.total("overflow_msgs"))
    if run.cell.traffic["kind"] == "sweep":
        info.update(events=run.total("events"),
                    events_per_s=run.total("events") / run.elapsed)
    else:
        info["warnings"] = sorted({w for r in run.records
                                   for w in r["warnings"]})[:4]
    say("bench " + json.dumps(info, default=str))
    for n, c in res["checks"].items():
        verdict = "ok" if passes(c["value"], c["limit"], c["must_be"]) \
            else "FAILED"
        say(f"check {n} = {c['value']} ({c['must_be'].replace('_', ' ')} "
            f"{c['limit']}) {verdict}")


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, control: Optional[str] = None,
             traffic_override: Optional[dict] = None,
             t_start: Optional[float] = None) -> dict:
    """One run of one cell; returns the result line's object. The caller
    has set up the process (`prepare_env`, then `configure_jax`)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(name, traffic_override=traffic_override)
    devices = chips(cell.chips, require_tpu)
    counter = CompileCounter()
    run = KINDS[cell.traffic["kind"]](cell, seed, seconds, trace, devices,
                                      control, t_start, counter)
    res = result(run, devices, trace)
    report(run, res)
    return res
