"""Measured autotuning over the engine's throughput knobs (r13).

docs/perf_notes.md is a graveyard of hand-pinned throughput knobs — ring
depth 2 with reply-parity, LOG window 16, ~32k-lane chip saturation,
300-step scan chunks, refill lane widths — each measured once on one chip
(v5e, rounds 4–5) and frozen, while the notes themselves warn that
several values contradict first-principles intuition and future changes
should RE-MEASURE rather than trust the current shape. This module is
that re-measurement, made a subsystem (the Ansor / OpenTuner tradition:
search the schedule space per device, cache the winner): successive-
halving coordinate descent driven by the perf_notes measurement
discipline codified in `madsim_tpu.measure` (fresh seeds per rep index,
exact-program warmup, medians over interleaved rounds), with winners
persisted in a versioned tuned-config cache consumed by
`run_batch`/`triage`/`explore`/`campaign`/`ttfb` via ``tuning="auto"``.

Two EXPLICITLY SEPARATED knob tiers (docs/tuning.md):

  Tier A — result-invariant DISPATCH knobs: lanes per chunk,
  `dispatch_steps` segment length, host pipeline on/off, refill lane
  width, mesh device count. All covered by the repo's bit-identity
  contract (a seed's trajectory never depends on batch position, chunk
  phase, mesh placement, or retirement order), so the tuner may apply
  them anywhere — even mid-campaign — and a tuned run's per-seed rows
  equal the default run's bit-for-bit (tests/test_tune.py pins the
  matrix).

  Tier B — trajectory-AFFECTING config knobs: the pool slot budget and
  per-class depths (`msg_capacity`, `msg_depth_msg`, `msg_depth_timer`,
  `msg_spare_slots`) and, through spec hooks, the raft LOG window and kv
  OPS ring. These change which sends drop and what the handlers see, so
  they are tuned ONLY at config-creation time, and a winner is cached
  only after the acceptance gate passes: `overflow == 0`, zero log
  saturation, AND a fresh range-certifier run on the tuned config
  (`tier_b_gate` — the `narrow_horizon_us` derating refusal included,
  via the BatchedSim constructor). Tuned Tier-B values are folded into
  the SimConfig the caller builds, so `SimConfig.hash()` changes and
  `campaign.check_resume_conflicts` / `Campaign.resume`'s config-hash
  check reject silent drift loudly.

Determinism: the search is a pure function of the measured walls — trial
order, seed derivation (`measure.fresh_seeds`), halving rule and the
final never-regress A/B guard are all fixed, and the guard returns the
hand-pinned default whenever the tuned assignment cannot beat it, so a
tuned entry is never a regression. Wall clocks are `time.perf_counter`
only (the ambient-entropy lint bar holds with zero pragmas — measurement
clocks never feed simulation state).

CLI: ``python -m madsim_tpu.tune --workload raft`` / ``make tune`` /
``make tune-smoke`` (the <60 s CPU gate).
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import telemetry
from .measure import SweepTimer, fresh_seeds, median

TUNED_FORMAT = "madsim-tpu-tuned/1"

# Tier-A dispatch knobs: result-invariant, applicable anywhere.
TIER_A_KNOBS = ("chunk", "dispatch_steps", "pipeline", "refill_lanes",
                "devices")
# Tier-B SimConfig knobs: trajectory-affecting, config-creation time only.
TIER_B_KNOBS = ("msg_capacity", "msg_depth_msg", "msg_depth_timer",
                "msg_spare_slots")

# tuning-trial wall-time histogram buckets (ms): trials span ~1 ms CPU
# smoke sweeps to multi-minute cold compiles
TRIAL_MS_BUCKETS = (1, 5, 10, 50, 100, 500, 1_000, 5_000, 30_000, 120_000)


class TunedCacheError(ValueError):
    """A tuned-config cache entry that must not be silently used: stale
    or unknown format version, or content that contradicts the requested
    key (a file copied from another device / workload / config)."""


# --------------------------------------------------------------------------
# cache identity
# --------------------------------------------------------------------------


def device_kind() -> str:
    """The accelerator identity a tuned entry is valid for (e.g.
    ``TPU_v5_lite`` or ``cpu``) — measured knobs do not transfer across
    device generations, which is the whole reason the cache is keyed."""
    import jax

    kind = str(jax.devices()[0].device_kind)
    return "".join(c if c.isalnum() else "_" for c in kind) or "unknown"


def lane_bucket(lanes: int) -> int:
    """Lane counts bucket to the next power of two: the knee points the
    knobs trade around (chip saturation, chunk sizing) move with scale,
    not with exact lane counts, and per-exact-count entries would make
    every sweep a cache miss."""
    lanes = int(lanes)
    if lanes < 1:
        raise ValueError(f"lane count must be >= 1, got {lanes}")
    b = 1
    while b < lanes:
        b *= 2
    return b


def config_hash_sans_tier_b(config) -> str:
    """SimConfig identity with the Tier-B pool knobs blanked: the cache
    key must be STABLE under the very values tuning changes, or a tuned
    config could never find its own entry again. Every other knob
    (horizon, chaos battery, latency model) keys the entry — a different
    workload shape deserves a different measurement."""
    lines = [
        ln for ln in config.to_toml().splitlines()
        if ln.split(" = ")[0] not in TIER_B_KNOBS
    ]
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]


def cache_key(device: str, workload: str, config, lanes: int) -> str:
    return (
        f"{device}-{workload}-{config_hash_sans_tier_b(config)}"
        f"-l{lane_bucket(lanes)}"
    )


def default_cache_dir() -> str:
    return os.environ.get("MADSIM_TUNED_DIR") or os.path.join(
        os.path.expanduser("~"), ".cache", "madsim-tpu", "tuned"
    )


@dataclasses.dataclass
class TunedEntry:
    """One measured winner: the `madsim-tpu-tuned/1` cache record.

    `dispatch` holds the Tier-A knob assignment (applied by
    `resolve_tuning` consumers at dispatch time); `config` the Tier-B
    SimConfig overrides and `spec` the Tier-B spec-knob overrides (both
    empty unless a Tier-B search ran AND its winner passed the
    acceptance gate — `certified` says so). `fallback` records that the
    never-regress guard kept the hand-pinned defaults."""

    device_kind: str
    workload: str
    config_hash: str  # sans Tier B (the cache key's config component)
    lane_bucket: int
    dispatch: Dict[str, Any] = dataclasses.field(default_factory=dict)
    config: Dict[str, Any] = dataclasses.field(default_factory=dict)
    spec: Dict[str, Any] = dataclasses.field(default_factory=dict)
    baseline_seeds_per_sec: float = 0.0
    tuned_seeds_per_sec: float = 0.0
    trials: int = 0
    fallback: bool = False
    certified: bool = False
    format: str = TUNED_FORMAT

    def key(self) -> str:
        return (
            f"{self.device_kind}-{self.workload}-{self.config_hash}"
            f"-l{self.lane_bucket}"
        )

    def win_pct(self) -> float:
        if self.baseline_seeds_per_sec <= 0:
            return 0.0
        return round(
            (self.tuned_seeds_per_sec / self.baseline_seeds_per_sec - 1)
            * 100, 2,
        )

    def to_doc(self) -> Dict[str, Any]:
        doc = dataclasses.asdict(self)
        doc["win_pct"] = self.win_pct()
        return doc

    @classmethod
    def from_doc(cls, doc: Dict[str, Any], where: str = "tuned entry"):
        doc = dict(doc)
        doc.pop("win_pct", None)
        fmt = doc.get("format")
        if fmt != TUNED_FORMAT:
            raise TunedCacheError(
                f"{where}: format {fmt!r} is not {TUNED_FORMAT!r} — a "
                "stale or foreign tuned-config cache must be re-tuned, "
                "never silently reinterpreted"
            )
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(doc) - known
        if unknown:
            raise TunedCacheError(
                f"{where}: unknown fields {sorted(unknown)} — written by "
                "a newer tree? re-tune rather than half-apply"
            )
        bad = set(doc.get("dispatch") or {}) - set(TIER_A_KNOBS)
        if bad:
            raise TunedCacheError(
                f"{where}: dispatch holds non-Tier-A knobs {sorted(bad)}"
            )
        bad = set(doc.get("config") or {}) - set(TIER_B_KNOBS)
        if bad:
            raise TunedCacheError(
                f"{where}: config holds non-Tier-B knobs {sorted(bad)}"
            )
        return cls(**doc)

    def save(self, dir: Optional[str] = None) -> str:
        dir = dir or default_cache_dir()
        os.makedirs(dir, exist_ok=True)
        path = os.path.join(dir, self.key() + ".json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_doc(), f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return path

    @classmethod
    def load(cls, path: str) -> "TunedEntry":
        with open(path) as f:
            doc = json.load(f)
        return cls.from_doc(doc, where=path)


def load_tuned(
    workload: str, config, lanes: int,
    dir: Optional[str] = None, device: Optional[str] = None,
) -> Optional[TunedEntry]:
    """The cache lookup behind ``tuning="auto"``: None on a clean miss
    (no entry for this device × workload × config × lane bucket);
    `TunedCacheError` when an entry EXISTS at the key but its content
    contradicts the request — wrong device_kind, wrong workload, wrong
    config hash, stale format — the r10 'silently dropped mesh' bug
    class, rejected loudly instead of half-applied."""
    dir = dir or default_cache_dir()
    device = device or device_kind()
    key = cache_key(device, workload, config, lanes)
    path = os.path.join(dir, key + ".json")
    if not os.path.exists(path):
        return None
    entry = TunedEntry.load(path)
    want = (device, workload, config_hash_sans_tier_b(config),
            lane_bucket(lanes))
    got = (entry.device_kind, entry.workload, entry.config_hash,
           entry.lane_bucket)
    if got != want:
        raise TunedCacheError(
            f"{path}: entry content {got} does not match its key {want} "
            "— a copied or hand-edited tuned cache; delete it and re-tune"
        )
    return entry


def _validate_dispatch(d: Dict[str, Any], where: str = "tuning") -> Dict[str, Any]:
    bad = set(d) - set(TIER_A_KNOBS)
    if bad:
        raise ValueError(
            f"{where}: {sorted(bad)} are not Tier-A dispatch knobs "
            f"(Tier A = {TIER_A_KNOBS}; Tier-B config knobs are applied "
            "at config-creation time only — see docs/tuning.md)"
        )
    return dict(d)


def resolve_tuning(
    tuning, workload: str, config, lanes: int,
    dir: Optional[str] = None,
) -> Dict[str, Any]:
    """Resolve a driver's `tuning` argument into Tier-A dispatch
    overrides ({} = run the hand-pinned defaults).

    Accepted forms: None (no-op), ``"auto"`` (consult the tuned-config
    cache; a clean miss is {}), a `TunedEntry`, a dict of Tier-A knobs
    (applied verbatim — this is what campaign checkpoints persist so
    kill/resume never re-tunes), or a path to a saved entry."""
    if tuning is None or tuning is False or tuning == "":
        return {}
    if isinstance(tuning, TunedEntry):
        return _validate_dispatch(tuning.dispatch, "TunedEntry.dispatch")
    if isinstance(tuning, dict):
        return _validate_dispatch(tuning)
    if tuning == "auto":
        entry = load_tuned(workload, config, lanes, dir=dir)
        return {} if entry is None else _validate_dispatch(
            entry.dispatch, "tuned cache"
        )
    if isinstance(tuning, str):
        return _validate_dispatch(
            TunedEntry.load(tuning).dispatch, tuning
        )
    raise TypeError(
        f"tuning must be None, 'auto', a dict, a TunedEntry or a path — "
        f"got {type(tuning).__name__}"
    )


# --------------------------------------------------------------------------
# the search: successive-halving coordinate descent
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Knob:
    """One tunable axis: candidate values in screening order."""

    name: str
    values: Tuple[Any, ...]
    tier: str = "A"


class TrialLog:
    """Trial bookkeeping + telemetry: every measured trial increments the
    per-knob `tune_trials_total` counter, lands its wall in the
    `tune_trial_ms` histogram, and runs inside a `telemetry.span` so the
    search shows up on the Perfetto wall-clock timeline next to the
    dispatches it is timing (docs/observability.md)."""

    def __init__(self, log: Optional[Callable[[str], None]] = None) -> None:
        self.rep = 1  # rep 0 is SweepTimer's warm rep — never timed
        self.trials: List[Dict[str, Any]] = []
        self.say = log or (lambda msg: None)

    def trial(self, measure, assignment: Dict[str, Any], knob: str,
              value) -> float:
        with telemetry.span("tune_trial", knob=knob, value=str(value)):
            wall = measure(assignment, self.rep)
        self.rep += 1
        reg = telemetry.get_registry()
        if reg is not None:
            reg.counter(
                "tune_trials_total", "autotune trials per knob"
            ).inc(knob=knob)
            reg.histogram(
                "tune_trial_ms", "measured autotune trial wall (ms)",
                buckets=TRIAL_MS_BUCKETS,
            ).observe(wall * 1e3, knob=knob)
        self.trials.append({
            "knob": knob, "value": value, "wall_s": round(wall, 6),
        })
        self.say(f"[tune] {knob}={value}: {wall * 1e3:.1f} ms")
        return wall


def coordinate_descent(
    knobs: Sequence[Knob],
    measure,
    base: Dict[str, Any],
    tl: TrialLog,
    passes: int = 1,
) -> Dict[str, Any]:
    """One knob at a time, others pinned at the current best; per knob, a
    successive-halving tournament: every surviving value gets one more
    interleaved measurement per round and the slower half is cut, so the
    budget concentrates on the contenders instead of re-measuring
    obvious losers (the Ansor/OpenTuner shape at coordinate scale)."""
    assign = dict(base)
    for _ in range(int(passes)):
        for knob in knobs:
            values = list(dict.fromkeys(
                list(knob.values) + [assign[knob.name]]
            ))
            if len(values) < 2:
                continue
            scores: Dict[Any, List[float]] = {v: [] for v in values}
            alive = list(values)
            while len(alive) > 1:
                for v in alive:  # interleaved round over survivors
                    a = dict(assign)
                    a[knob.name] = v
                    scores[v].append(tl.trial(measure, a, knob.name, v))
                alive = sorted(
                    alive, key=lambda v: median(scores[v])
                )[: (len(alive) + 1) // 2]
            assign[knob.name] = alive[0]
    return assign


def ab_guard(
    measure, default: Dict[str, Any], tuned: Dict[str, Any],
    tl: TrialLog, rounds: int = 2,
) -> Dict[str, float]:
    """The never-regress gate: default vs tuned head-to-head, interleaved
    rounds, median walls. The caller keeps the default whenever the tuned
    assignment does not beat it — a tuned entry may be a no-op, never a
    slowdown."""
    walls: Dict[str, List[float]] = {"default": [], "tuned": []}
    for _ in range(int(rounds)):
        walls["default"].append(
            tl.trial(measure, default, "ab_guard", "default")
        )
        walls["tuned"].append(tl.trial(measure, tuned, "ab_guard", "tuned"))
    return {k: median(v) for k, v in walls.items()}


# --------------------------------------------------------------------------
# Tier-B acceptance gate
# --------------------------------------------------------------------------


def certify_config(spec, config, lanes: int = 64) -> Tuple[bool, List[str]]:
    """Fresh range-certifier run over (spec, config): the tuned config's
    own step program is abstractly traced (`analysis.jaxpr_check.
    trace_sim`) and every Layer-3 interval claim re-proved — narrow-dtype
    certified horizons (skew-derated) covering the config's horizon,
    clock no-wrap, dynamic-index bounds. A tuned pool layout is a new
    program; it re-earns its certificate or it is not cached."""
    from .analysis.jaxpr_check import trace_sim
    from .analysis.ranges import verify_ranges
    from .tpu.engine import BatchedSim

    sim = BatchedSim(spec, config, triage=True, coverage=True)
    trace = trace_sim(sim, name=f"{spec.name}-tuned", lanes=lanes)
    results, _cert = verify_ranges(trace)
    reasons = [
        f"range certifier: {v.where}: {v.detail}"
        for r in results for v in r.violations
    ]
    return (not reasons), reasons


def tier_b_gate(
    workload, config, seeds: int = 256,
    certify: bool = True, log: Optional[Callable[[str], None]] = None,
) -> Dict[str, Any]:
    """The Tier-B acceptance gate. A trajectory-affecting tuned config is
    cached ONLY when all three hold:

      1. the engine ACCEPTS it — `BatchedSim.__init__`'s validation,
         including the `narrow_horizon_us` clock-skew derating refusal;
      2. an acceptance sweep shows the config drops NOTHING the network
         didn't roll to drop: `overflow == 0` (pool + straggler drops)
         and zero log/window saturation (any summarize key naming
         ``saturated``) — the headline zero-drop discipline;
      3. the range certifier re-certifies the tuned config
         (`certify_config`).

    Returns {"ok", "reasons", "summary"}; reasons name the failing leg.
    """
    import dataclasses as dc

    from .tpu.batch import run_batch
    from .tpu.engine import BatchedSim

    say = log or (lambda msg: None)
    reasons: List[str] = []
    try:
        BatchedSim(workload.spec, config)
    except ValueError as e:
        return {
            "ok": False,
            "reasons": [f"engine rejects the config: {e}"],
            "summary": {},
        }
    wl2 = dc.replace(workload, config=config, host_repro=None)
    res = run_batch(
        range(int(seeds)), wl2, repro_on_host=False, max_traces=0,
        mesh=None, shrink_on_violation=False,
    )
    overflow = int(res.summary.get("total_overflow", 0))
    if overflow:
        reasons.append(
            f"acceptance sweep dropped {overflow} sends (overflow != 0): "
            "the tuned pool budget is too small for this traffic"
        )
    for k, v in sorted(res.summary.items()):
        if "saturated" in k and isinstance(v, (int, float)) and v:
            reasons.append(f"acceptance sweep: {k} = {v} (must be 0)")
    if certify and not reasons:
        ok, cert_reasons = certify_config(workload.spec, config)
        if not ok:
            reasons.extend(cert_reasons)
    gate = {
        "ok": not reasons,
        "reasons": reasons,
        "summary": {
            "seeds": int(seeds),
            "violations": int(res.violations),
            "total_overflow": overflow,
        },
    }
    if reasons:
        say(f"[tune] Tier-B gate REJECTED: {'; '.join(reasons)}")
    return gate


# --------------------------------------------------------------------------
# Tier-A tuning: the spread-mix benchmark and whole workloads
# --------------------------------------------------------------------------


def spread_mix_sim(virtual_secs: float = 1.0):
    """The 10x horizon-spread raft mix (the continuous-batching headline
    workload: one long admission per 8, crash + loss plan — the
    ddmin-probe / short-mutant shape) as the Tier-A tuning benchmark.
    Returns (BatchedSim(triage=True), horizon_us)."""
    from . import nemesis as nem
    from .tpu import make_raft_spec
    from .tpu import nemesis as tn
    from .tpu.engine import BatchedSim
    from .tpu.spec import SimConfig

    horizon = int(virtual_secs * 1e6)
    plan = nem.FaultPlan(name="tune-mix", clauses=(
        nem.Crash(interval_lo_us=horizon // 6, interval_hi_us=horizon // 2,
                  down_lo_us=horizon // 8, down_hi_us=horizon // 3),
        nem.MsgLoss(rate=0.05),
    ))
    cfg = tn.compile_plan(plan, SimConfig(horizon_us=horizon))
    return BatchedSim(make_raft_spec(), cfg, triage=True), horizon


def spread_ctl_from_h(h):
    """Per-admission TriageCtl rows for a horizon column `h` (int64 us)
    — the one definition of the spread mix's ctl shape, shared with
    benches/roofline.py's refill_occupancy/mesh_scaling rows so the
    tuning benchmark and the occupancy/scaling tables can never drift
    onto different workloads."""
    import jax.numpy as jnp

    from .nemesis import OCC_CLAUSES, RATE_CLAUSES
    from .tpu.engine import TriageCtl
    from .tpu.spec import REBASE_US

    h = np.asarray(h, np.int64)
    n = len(h)
    return TriageCtl(
        off=jnp.zeros((n,), jnp.int32),
        occ=jnp.zeros((n, len(OCC_CLAUSES)), jnp.int32),
        rate_scale=jnp.ones((n, len(RATE_CLAUSES)), jnp.float32),
        h_epoch=jnp.asarray((h // REBASE_US).astype(np.int32)),
        h_off=jnp.asarray((h % REBASE_US).astype(np.int32)),
    )


def spread_ctl_rows(horizon_us: int, admissions: int, spread: int = 10,
                    long_every: int = 8):
    """Per-admission TriageCtl rows for the spread mix: one long horizon
    per `long_every` admissions, the rest at horizon/spread."""
    h = np.where(
        np.arange(int(admissions)) % int(long_every) == 0,
        int(horizon_us), int(horizon_us) // int(spread),
    ).astype(np.int64)
    return spread_ctl_from_h(h)


def tune_spread_mix(
    lanes: int = 16, waves: int = 16, spread: int = 10, long_every: int = 8,
    virtual_secs: float = 1.0, max_steps: int = 50_000,
    knobs: Optional[Sequence[Knob]] = None,
    guard_rounds: int = 2,
    cache_dir: Optional[str] = None, save: bool = True,
    log: Optional[Callable[[str], None]] = None,
) -> TunedEntry:
    """One Tier-A coordinate pass over the refill engine's dispatch knobs
    on the spread mix — the `make tune-smoke` target's search. Knobs:
    refill lane width (queue padding follows it: the queue pads to a
    lane-width multiple) and the sweep segment length."""
    sim, horizon = spread_mix_sim(virtual_secs)
    A = int(lanes) * int(waves)
    ctl = spread_ctl_rows(horizon, A, spread=spread, long_every=long_every)
    from .tpu.engine import DEFAULT_DISPATCH_STEPS

    default = {
        "refill_lanes": int(lanes),
        "dispatch_steps": DEFAULT_DISPATCH_STEPS,
    }
    if knobs is None:
        widths = tuple(sorted({max(1, lanes // 2), int(lanes), lanes * 2}))
        knobs = (
            Knob("refill_lanes", widths),
            Knob("dispatch_steps", (1_000, 5_000, 10_000)),
        )

    def run(assign: Dict[str, Any], rep: int):
        seeds = fresh_seeds(rep, A)
        return sim.run_refill(
            seeds, lanes=int(assign["refill_lanes"]), max_steps=max_steps,
            dispatch_steps=int(assign["dispatch_steps"]), ctl=ctl,
        )

    measure = SweepTimer(
        run,
        compile_key=lambda a: (a["refill_lanes"], a["dispatch_steps"]),
    )
    tl = TrialLog(log)
    best = coordinate_descent(knobs, measure, default, tl)
    best, fallback, baseline_sps, tuned_sps = _guard_tier_a(
        measure, default, best, tl, work_items=A,
        guard_rounds=guard_rounds,
    )
    return _finish_entry(
        workload="spread-mix", config=sim.config, lanes=lanes,
        default=default, best=best, fallback=fallback,
        baseline_sps=baseline_sps, tuned_sps=tuned_sps, tl=tl,
        cache_dir=cache_dir, save=save,
    )


def _guard_tier_a(
    measure, default: Dict[str, Any], best: Dict[str, Any],
    tl: TrialLog, work_items: int, guard_rounds: int,
) -> Tuple[Dict[str, Any], bool, float, float]:
    """The never-regress A/B guard + seeds/s accounting, shared by every
    tuner. Returns (best, fallback, baseline_sps, tuned_sps) with `best`
    replaced by the default when the tuned assignment did not measure
    faster. Runs BEFORE any Tier-B pass so Tier-B candidates are
    measured under the Tier-A assignment the entry actually ships —
    guarding after would let the guard discard the dispatch shape the
    Tier-B win was measured (and certified) under."""
    if best != default:
        meds = ab_guard(measure, default, best, tl, rounds=guard_rounds)
        fallback = meds["tuned"] >= meds["default"]
        baseline_sps = work_items / meds["default"]
        tuned_sps = (
            baseline_sps if fallback else work_items / meds["tuned"]
        )
        if fallback:
            best = dict(default)
    else:
        wall = tl.trial(measure, default, "ab_guard", "default")
        baseline_sps = tuned_sps = work_items / wall
        fallback = True
    return best, fallback, baseline_sps, tuned_sps


def _finish_entry(
    workload: str, config, lanes: int,
    default: Dict[str, Any], best: Dict[str, Any],
    fallback: bool, baseline_sps: float, tuned_sps: float,
    tl: TrialLog,
    cache_dir: Optional[str], save: bool,
    config_overrides: Optional[Dict[str, Any]] = None,
    spec_overrides: Optional[Dict[str, Any]] = None,
    certified: bool = False,
) -> TunedEntry:
    """The shared tail of every tuner: cache-entry assembly + write from
    the `_guard_tier_a` verdict."""
    entry = TunedEntry(
        device_kind=device_kind(),
        workload=workload,
        config_hash=config_hash_sans_tier_b(config),
        lane_bucket=lane_bucket(lanes),
        # store only the knobs that actually BEAT their default: a value
        # equal to the default was either never searched (quick grids) or
        # lost, and consumers treat every cached key as a measured winner
        dispatch={
            k: v for k, v in best.items() if v != default.get(k)
        } if not fallback else {},
        config=dict(config_overrides or {}),
        spec=dict(spec_overrides or {}),
        baseline_seeds_per_sec=round(baseline_sps, 2),
        tuned_seeds_per_sec=round(tuned_sps, 2),
        trials=len(tl.trials),
        fallback=fallback and not (config_overrides or spec_overrides),
        certified=certified,
    )
    if save:
        entry.save(cache_dir)
    return entry


def _mesh_for(devices: int, cached: bool = False):
    """0 = the production default (`resolve_mesh("auto")`: every visible
    device); d >= 1 = an explicit 1-D lane mesh over the first d.

    `cached=True` is the consumer-side mode (a driver applying a
    tuned-cache entry): `device_kind()` keys the cache by chip KIND, not
    count, so an entry recorded on a bigger host of the same kind (an
    8-chip pod, a forced multi-device CPU) can name more devices than
    this host has. A Tier-A knob's contract is "a miss runs the
    hand-pinned defaults — never a regression", so the unsatisfiable
    count falls back to the production default mesh instead of raising;
    the tuner's own search (cached=False) still raises, because there a
    bad count is a caller bug."""
    import jax

    d = int(devices)
    if d == 0:
        return "auto"
    if d == 1:
        return None
    devs = jax.devices()
    if d > len(devs):
        if cached:
            return "auto"
        raise ValueError(f"devices={d} but only {len(devs)} visible")
    return jax.sharding.Mesh(np.array(devs[:d]), ("seeds",))


def tier_a_knobs(
    workload, n_seeds: int, quick: bool = False,
) -> Tuple[Knob, ...]:
    """The Tier-A knob grid for a whole-workload `run_batch` sweep.
    `quick` is the CI/bench screen: segment length + pipeline only."""
    import jax

    n_seeds = int(n_seeds)
    steps = (5_000, 10_000, 20_000) if quick else (
        2_000, 5_000, 10_000, 20_000,
    )
    ks: List[Knob] = [
        Knob("dispatch_steps", steps),
        Knob("pipeline", (True, False)),
    ]
    if not quick:
        chunks = tuple(sorted({
            max(1, n_seeds // 4), max(1, n_seeds // 2), n_seeds,
        }))
        ks.append(Knob("chunk", chunks))
        if workload.lane_check is None:
            # the refill path keeps no per-admission node state, so
            # lane_check workloads must stay chunked (run_batch refuses)
            ks.append(Knob("refill_lanes", (0, max(1, n_seeds // 4))))
        D = len(jax.devices())
        if D > 1:
            # 0 is "auto" = a mesh over ALL visible devices, so an
            # explicit D would measure the same configuration twice (and
            # a noise win could cache a phantom devices=D "winner" that
            # equals the default) — the ladder stays strictly below D
            dv: List[int] = [0, 1]
            d = 2
            while d < D:
                dv.append(d)
                d *= 2
            ks.append(Knob("devices", tuple(dv)))
    return tuple(ks)


def tune_workload(
    workload, name: str, lanes: int = 4_096,
    n_seeds: Optional[int] = None, tier: str = "A",
    knobs: Optional[Sequence[Knob]] = None,
    spec_knobs: Optional[Sequence["SpecKnob"]] = None,
    quick: bool = False, guard_rounds: int = 2, gate_seeds: int = 256,
    cache_dir: Optional[str] = None, save: bool = True,
    log: Optional[Callable[[str], None]] = None,
) -> TunedEntry:
    """Tune one BatchWorkload's end-to-end `run_batch` throughput.

    Tier A searches the dispatch knobs with one shared compiled sim (the
    trial clock is `measure.SweepTimer`: fresh seed blocks per rep,
    exact-program warm per compile key). With ``tier="AB"`` a Tier-B
    pass follows, holding the Tier-A winners fixed: pool-knob candidates
    are screened for engine validity, searched by the same
    successive-halving descent (one compiled sim per candidate config,
    warmed before timing), and the winner is cached ONLY after
    `tier_b_gate` passes — otherwise the defaults stand."""
    import dataclasses as dc

    import jax

    from .tpu.batch import default_chunk, run_batch
    from .tpu.engine import BatchedSim
    from .tpu.spec import SimConfig

    cfg = workload.config or SimConfig()
    n = int(n_seeds or int(lanes))
    tl = TrialLog(log)
    from .tpu.engine import DEFAULT_DISPATCH_STEPS

    default = {
        # run_batch's own default on this host (devices 0 = every one)
        "chunk": min(default_chunk(len(jax.devices())), n),
        "dispatch_steps": DEFAULT_DISPATCH_STEPS,
        "pipeline": True, "refill_lanes": 0, "devices": 0,
    }
    if knobs is None:
        knobs = tier_a_knobs(workload, n_seeds=n, quick=quick)
    sim = BatchedSim(workload.spec, cfg)

    def run(assign: Dict[str, Any], rep: int):
        run_batch(
            fresh_seeds(rep, n), workload, sim=sim,
            chunk=int(assign["chunk"]),
            dispatch_steps=int(assign["dispatch_steps"]),
            pipeline=bool(assign["pipeline"]),
            refill=int(assign["refill_lanes"]),
            mesh=_mesh_for(assign["devices"]),
            repro_on_host=False, max_traces=0,
        )
        return None  # run_batch reads its results back itself

    measure = SweepTimer(
        run,
        compile_key=lambda a: (
            a["chunk"], a["dispatch_steps"], a["refill_lanes"], a["devices"],
        ),
    )
    best = coordinate_descent(knobs, measure, default, tl)
    # guard FIRST: Tier-B candidates below must be measured (and gated)
    # under the Tier-A assignment the entry actually ships, which is only
    # known once the never-regress A/B has had its say
    best, fallback, baseline_sps, tuned_sps = _guard_tier_a(
        measure, default, best, tl, work_items=n,
        guard_rounds=guard_rounds,
    )

    config_overrides: Dict[str, Any] = {}
    spec_overrides: Dict[str, Any] = {}
    certified = False
    if "B" in tier.upper():
        config_overrides, spec_overrides, certified = _tune_tier_b(
            workload, best, n, tl, spec_knobs=spec_knobs,
            gate_seeds=gate_seeds, log=log,
        )
    # cache identity is the SPEC name ("raft5"), not the registry/CLI
    # name ("raft"): every tuning="auto" consumer (run_batch, Campaign,
    # Explorer, ttfb, shrink_seed) resolves with workload.spec.name, so
    # the entry must be written under the same key it is looked up by.
    # The lane bucket is the MEASURED sweep size `n`, not the requested
    # `lanes`: knobs do not transfer across scale (that is why buckets
    # exist), so a --seeds 512 run must never write under l32768
    return _finish_entry(
        workload=workload.spec.name, config=cfg, lanes=n,
        default=default, best=best, fallback=fallback,
        baseline_sps=baseline_sps, tuned_sps=tuned_sps, tl=tl,
        cache_dir=cache_dir, save=save,
        config_overrides=config_overrides, spec_overrides=spec_overrides,
        certified=certified,
    )


# --------------------------------------------------------------------------
# Tier B: trajectory-affecting knobs, gated
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SpecKnob:
    """A Tier-B SPEC knob (raft LOG window, kv OPS ring): candidate
    values plus a rebuild hook (workload, value) -> workload carrying the
    re-parameterized spec. Measured and gated exactly like the SimConfig
    pool knobs; winners are recorded in `TunedEntry.spec` for the
    config-creation-time caller to apply through its own factory."""

    name: str
    values: Tuple[Any, ...]
    rebuild: Callable[[Any, Any], Any]
    default: Any = None


def tier_b_effective_defaults(workload, default: Dict[str, Any],
                              ) -> Dict[str, Any]:
    """The engine's EFFECTIVE values behind None-defaulted Tier-B pool
    knobs (msg_depth_msg/msg_depth_timer None = `msg_capacity // C`,
    derived inside BatchedSim). A candidate equal to the effective value
    is the SAME program as the default — the search screens it (a
    duplicate compile) and the recorder never caches it as an override
    (a behavioral no-op that would still move `SimConfig.hash()` and
    make resume/bundles treat an identical program as a new config)."""
    from .tpu.engine import BatchedSim
    from .tpu.spec import SimConfig

    eff = dict(default)
    if eff.get("msg_depth_msg") is None or (
        "msg_depth_timer" in eff and eff["msg_depth_timer"] is None
    ):
        sim0 = BatchedSim(
            workload.spec, workload.config or SimConfig()
        )
        if eff.get("msg_depth_msg") is None:
            eff["msg_depth_msg"] = int(sim0._Km)
        if "msg_depth_timer" in eff and eff["msg_depth_timer"] is None:
            eff["msg_depth_timer"] = int(sim0._Kt)
    return eff


def tier_b_config_knobs(workload) -> Tuple[Knob, ...]:
    """Pool-knob candidates around the workload's current EFFECTIVE
    values (the depths the engine actually derives, not an
    approximation). Fused (on_event) specs place node-pooled slots —
    depth + spare are the levers; two-handler specs tune the per-class
    ring depths."""
    from .tpu.engine import BatchedSim
    from .tpu.spec import SimConfig

    cfg = workload.config or SimConfig()
    fused = workload.spec.on_event is not None
    sim0 = BatchedSim(workload.spec, cfg)
    depth = int(sim0._Km)
    ks = [Knob(
        "msg_depth_msg",
        tuple(sorted({max(1, depth - 1), depth, depth + 1})), tier="B",
    )]
    if fused:
        spare = cfg.msg_spare_slots
        ks.append(Knob(
            "msg_spare_slots",
            tuple(sorted({max(0, spare - 1), spare, spare + 1, spare + 2})),
            tier="B",
        ))
    else:
        kt = int(sim0._Kt)
        ks.append(Knob(
            "msg_depth_timer",
            tuple(sorted({max(1, kt - 1), kt, kt + 1})), tier="B",
        ))
    return tuple(ks)


def _tune_tier_b(
    workload, tier_a: Dict[str, Any], n_seeds: int, tl: TrialLog,
    spec_knobs: Optional[Sequence[SpecKnob]] = None,
    gate_seeds: int = 256,
    log: Optional[Callable[[str], None]] = None,
) -> Tuple[Dict[str, Any], Dict[str, Any], bool]:
    """The Tier-B search + gate: returns (config_overrides,
    spec_overrides, certified). Defaults win unless a gated candidate
    measures faster AND passes `tier_b_gate` on the full tuned config."""
    import dataclasses as dc

    from .tpu.batch import run_batch
    from .tpu.engine import BatchedSim
    from .tpu.spec import SimConfig

    say = log or (lambda msg: None)
    base_cfg = workload.config or SimConfig()
    knobs = tier_b_config_knobs(workload)
    default = {k.name: getattr(base_cfg, k.name) for k in knobs}
    for sk in (spec_knobs or ()):
        default[sk.name] = sk.default
    sims: Dict[Any, Tuple[Any, Any]] = {}
    spec_by_name = {sk.name: sk for sk in (spec_knobs or ())}

    def build(assign: Dict[str, Any]):
        wl2 = workload
        cfg_over = {
            k: v for k, v in assign.items() if k not in spec_by_name
        }
        for k, sk in spec_by_name.items():
            if assign.get(k) != sk.default:
                wl2 = sk.rebuild(wl2, assign[k])
        cfg2 = dc.replace(wl2.config or base_cfg, **cfg_over)
        wl2 = dc.replace(wl2, config=cfg2, host_repro=None)
        return wl2, cfg2

    def valid(assign: Dict[str, Any]) -> bool:
        try:
            wl2, cfg2 = build(assign)
            BatchedSim(wl2.spec, cfg2)
            return True
        except ValueError:
            return False

    def run(assign: Dict[str, Any], rep: int):
        key = tuple(sorted(assign.items()))
        ent = sims.get(key)
        if ent is None:
            wl2, cfg2 = build(assign)
            ent = sims[key] = (BatchedSim(wl2.spec, cfg2), wl2)
        simb, wl2 = ent
        run_batch(
            fresh_seeds(rep, int(n_seeds)), wl2, sim=simb,
            chunk=int(tier_a["chunk"]),
            dispatch_steps=int(tier_a["dispatch_steps"]),
            pipeline=bool(tier_a["pipeline"]),
            refill=int(tier_a["refill_lanes"]),
            # Tier-B candidates are timed under the FULL Tier-A winner,
            # mesh included — a pool layout that wins single-device but
            # loses sharded must not be cached as a measured win
            mesh=_mesh_for(tier_a["devices"]),
            repro_on_host=False, max_traces=0,
        )
        return None

    measure = SweepTimer(
        run, compile_key=lambda a: tuple(sorted(a.items())),
    )
    all_knobs = list(knobs) + [
        Knob(sk.name, sk.values, tier="B") for sk in (spec_knobs or ())
    ]
    # screen candidate values for engine validity against the default
    # point (a refused combination never burns a trial) AND for
    # effective-default twins: a None-defaulted depth's engine-derived
    # value names the default program, so measuring it is a duplicate
    # compile and caching it would be a hash-moving no-op
    effective = tier_b_effective_defaults(workload, default)
    screened: List[Knob] = []
    for k in all_knobs:
        vals = tuple(
            v for v in k.values
            if not (
                default.get(k.name) is None and v == effective.get(k.name)
            )
            and valid({**default, k.name: v})
        )
        if vals:
            screened.append(dataclasses.replace(k, values=vals))
    best = coordinate_descent(screened, measure, default, tl)
    if best == default:
        return {}, {}, False
    meds = ab_guard(measure, default, best, tl)
    if meds["tuned"] >= meds["default"]:
        say("[tune] Tier B: no candidate beat the hand-pinned defaults")
        return {}, {}, False
    wl2, cfg2 = build(best)
    gate = tier_b_gate(wl2, cfg2, seeds=gate_seeds, log=log)
    if not gate["ok"]:
        return {}, {}, False
    config_overrides = {
        k: best[k] for k in default
        if k not in spec_by_name and best[k] != default[k]
        and best[k] != effective.get(k, default[k])
    }
    spec_overrides = {
        k: best[k] for k in spec_by_name if best[k] != default[k]
    }
    say(
        f"[tune] Tier B certified: config={config_overrides} "
        f"spec={spec_overrides}"
    )
    return config_overrides, spec_overrides, True


def apply_tier_b(config, entry: TunedEntry):
    """Fold a certified entry's Tier-B overrides into a SimConfig — the
    config-creation-time application (`SimConfig.hash()` changes, so
    campaign resume and repro bundles see the drift loudly). Refuses an
    uncertified entry: Tier B without its gate is not a tuning, it is a
    behavior change."""
    if entry.config and not entry.certified:
        raise ValueError(
            "tuned entry carries Tier-B overrides but certified=False — "
            "the acceptance gate must pass before Tier B is applied"
        )
    if not entry.config:
        return config
    return dataclasses.replace(config, **entry.config)


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------


def _tune_workloads() -> Tuple[str, ...]:
    # CLI sweep membership comes from the consolidated workload registry
    from . import workloads as registry

    return registry.names(tunable=True)


WORKLOADS = _tune_workloads()


def _spec_knobs_for(name: str, virtual_secs: float) -> Tuple[SpecKnob, ...]:
    """The in-tree Tier-B spec hooks: raft's LOG window and kv's OPS
    history ring, rebuilt through the same factories the named workloads
    use (docs/tuning.md); any other workload's hooks come from its
    registry row (speclang-generated entries derive them from the spec
    source's knob declarations)."""
    import dataclasses as dc

    if name == "raft":
        from .tpu import make_raft_spec

        def rebuild(wl, v):
            return dc.replace(
                wl, spec=make_raft_spec(n_nodes=5, log_capacity=int(v))
            )

        return (SpecKnob(
            "log_capacity", (12, 16, 24), rebuild, default=24,
        ),)
    if name == "kv":
        from .tpu.kv import kv_workload

        def rebuild(wl, v):
            fresh = kv_workload(
                virtual_secs=virtual_secs, ops_capacity=int(v),
            )
            return dc.replace(
                wl, spec=fresh.spec, lane_check=fresh.lane_check,
            )

        base = max(24, min(128, int(virtual_secs * 6.4)))
        return (SpecKnob(
            "ops_capacity",
            tuple(sorted({24, base, min(128, base * 2)})),
            rebuild, default=base,
        ),)
    from . import workloads as registry

    try:
        return tuple(registry.spec_knobs(name, virtual_secs))
    except KeyError:
        return ()


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m madsim_tpu.tune",
        description="measured autotuning over the engine's throughput "
        "knobs; winners cached per (device_kind, workload, config, lane "
        "bucket) and consumed via tuning='auto' (docs/tuning.md)",
    )
    parser.add_argument(
        "--workload", default="raft",
        help=f"{'|'.join(WORKLOADS)}|spread-mix|all",
    )
    parser.add_argument("--virtual-secs", type=float, default=2.0)
    parser.add_argument("--storm", action="store_true")
    parser.add_argument(
        "--lanes", type=int, default=None,
        help="seeds per trial sweep / cache lane bucket (default: 4096; "
        "spread-mix: 16 refill lanes)",
    )
    parser.add_argument(
        "--seeds", type=int, default=None,
        help="seeds per trial sweep (default: --lanes)",
    )
    parser.add_argument("--tier", default="A", choices=("A", "B", "AB"))
    parser.add_argument("--cache-dir", default=None)
    parser.add_argument("--no-save", action="store_true")
    parser.add_argument(
        "--quick", action="store_true",
        help="small knob grid (segment length + pipeline only)",
    )
    parser.add_argument("--quiet", action="store_true")
    args = parser.parse_args(argv)
    from .compile_cache import configure_compile_cache

    configure_compile_cache()

    say = (lambda msg: None) if args.quiet else print
    names = list(WORKLOADS) if args.workload == "all" else [args.workload]
    rc = 0
    for nm in names:
        try:
            if nm == "spread-mix":
                # the spread-mix branch runs the refill engine's own
                # search; the workload-sweep flags below don't apply to
                # it and must not be silently dropped
                dropped = [
                    flag for flag, hit in (
                        ("--tier", args.tier != "A"),
                        ("--seeds", args.seeds is not None),
                        ("--quick", args.quick),
                        ("--storm", args.storm),
                    ) if hit
                ]
                if dropped:
                    parser.error(
                        f"{' '.join(dropped)} do(es) not apply to "
                        "--workload spread-mix (Tier-A refill search "
                        "only; see docs/tuning.md)"
                    )
                entry = tune_spread_mix(
                    lanes=args.lanes or 16,
                    virtual_secs=args.virtual_secs,
                    cache_dir=args.cache_dir, save=not args.no_save,
                    log=say,
                )
            else:
                from .explore import _named_workload

                wl = _named_workload(nm, args.virtual_secs, args.storm)
                entry = tune_workload(
                    wl, nm, lanes=args.lanes or 4_096, n_seeds=args.seeds,
                    tier=args.tier,
                    spec_knobs=(
                        _spec_knobs_for(nm, args.virtual_secs)
                        if "B" in args.tier else None
                    ),
                    quick=args.quick, cache_dir=args.cache_dir,
                    save=not args.no_save, log=say,
                )
        except Exception as e:  # noqa: BLE001 - one workload must not
            # hide the others' results
            print(json.dumps({
                "workload": nm,
                "error": f"{type(e).__name__}: {str(e)[:200]}",
            }), flush=True)
            rc = 1
            continue
        print(json.dumps(entry.to_doc()), flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
