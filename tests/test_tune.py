"""Autotune (madsim_tpu/tune) + the codified measurement discipline
(madsim_tpu/measure).

The subsystem's contract (docs/tuning.md):
  * Tier-A dispatch knobs are RESULT-INVARIANT: per-seed rows are
    bit-identical across chunk width, segment length, pipeline mode,
    refill lane width — the matrix that lets `tuning="auto"` apply
    anywhere, even mid-campaign;
  * the tuned-config cache (`madsim-tpu-tuned/1`) round-trips exactly,
    and rejects stale formats / wrong-device entries LOUDLY instead of
    half-applying them;
  * the Tier-B gate refuses a drop-inducing pool config next to its
    clean twin (overflow == 0 is non-negotiable for cached configs);
  * campaigns persist the resolved tuning and reject a resume under a
    different tuned cache (the r10 silently-dropped-mesh bug class);
  * the measurement discipline warms the EXACT timed program and derives
    fresh seeds per rep — the node_sharding warmed-with-a-different-
    step-count compile-timing bug (perf_notes §1-D) as a regression
    test instead of a footnote.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from madsim_tpu import measure, tune


def _raft_workload(virtual_secs: float = 0.5):
    from madsim_tpu.tpu import raft_workload

    return dataclasses.replace(
        raft_workload(virtual_secs=virtual_secs), host_repro=None
    )


# ---------------------------------------------------------- the discipline


def test_fresh_seeds_are_disjoint_per_rep():
    a, b = measure.fresh_seeds(0, 8), measure.fresh_seeds(1, 8)
    assert a.dtype == np.uint32 and b.dtype == np.uint32
    assert not set(a.tolist()) & set(b.tolist())
    assert measure.median([3.0, 1.0, 2.0]) == 2.0
    with pytest.raises(ValueError):
        measure.fresh_seeds(0, 0)


def test_time_scan_ms_warms_the_exact_timed_program():
    """THE node_sharding regression (perf_notes §1-D caveat): run_steps
    jits per (shape, n_steps), so the warmup must run the exact
    (shape, scan) program before any timed rep — and every timed rep
    must init from a FRESH seed block (every timed rep does new work)."""
    calls = []

    def init(seeds):
        calls.append(("init", int(seeds[0])))
        return "st"

    def run_steps(st, n):
        calls.append(("run", int(n)))
        return st

    measure.time_scan_ms(
        init, run_steps, lanes=4, scan=60, warm_steps=10, rounds=2,
        block=lambda x: None,
    )
    runs = [n for kind, n in calls if kind == "run"]
    inits = [s for kind, s in calls if kind == "init"]
    # the timed (shape, 60) program ran during the warm phase — before
    # the first timed rep's init
    first_timed_init = calls.index(("init", inits[1]))
    assert ("run", 60) in calls[:first_timed_init], (
        "warmup never ran the exact timed (shape, scan) program — the "
        "first timed rep would contain its XLA compile"
    )
    # warmup + 2 reps, each running warm_steps then scan
    assert runs == [10, 60, 10, 60, 10, 60]
    # fresh seeds per rep: three distinct seed blocks (warm, rep1, rep2)
    assert len(set(inits)) == 3


def test_sweep_timer_warms_once_per_compile_key():
    log = []

    def run(assign, rep):
        log.append((assign["k"], rep))
        return None

    timer = measure.SweepTimer(
        run, compile_key=lambda a: a["k"], block=lambda x: None
    )
    timer({"k": 1}, rep=1)
    timer({"k": 1}, rep=2)
    timer({"k": 2}, rep=3)
    # key 1 warmed once (rep 0), key 2 warmed once; timed reps untouched
    assert log == [(1, 0), (1, 1), (1, 2), (2, 0), (2, 3)]


def test_interleaved_medians_interleaves_and_advances_reps():
    seen = []
    meds = measure.interleaved_medians(
        {"a": lambda r: seen.append(("a", r)),
         "b": lambda r: seen.append(("b", r))},
        rounds=2, block=lambda x: None,
    )
    assert [s[0] for s in seen] == ["a", "b", "a", "b"]
    assert len({r for _, r in seen}) == 4  # globally unique rep indices
    assert set(meds) == {"a", "b"}


# ------------------------------------------------------------ cache + keys


def test_lane_bucket_and_config_hash_sans_tier_b():
    from madsim_tpu.tpu.spec import SimConfig

    assert tune.lane_bucket(1) == 1
    assert tune.lane_bucket(300) == 512
    assert tune.lane_bucket(4096) == 4096
    cfg = SimConfig()
    tuned = dataclasses.replace(
        cfg, msg_capacity=256, msg_depth_msg=3, msg_depth_timer=2,
        msg_spare_slots=4,
    )
    # the key is STABLE under the very knobs Tier B changes...
    assert tune.config_hash_sans_tier_b(cfg) == \
        tune.config_hash_sans_tier_b(tuned)
    # ...and sensitive to everything else
    assert tune.config_hash_sans_tier_b(cfg) != \
        tune.config_hash_sans_tier_b(
            dataclasses.replace(cfg, horizon_us=1)
        )
    # Tier-B values DO move the full config hash (resume-conflict guard)
    assert cfg.hash() != tuned.hash()


def test_tuned_cache_roundtrip_and_miss(tmp_path):
    from madsim_tpu.tpu.spec import SimConfig

    cfg = SimConfig()
    entry = tune.TunedEntry(
        device_kind=tune.device_kind(), workload="raft",
        config_hash=tune.config_hash_sans_tier_b(cfg),
        lane_bucket=tune.lane_bucket(40),
        dispatch={"chunk": 32, "pipeline": False},
        baseline_seeds_per_sec=10.0, tuned_seeds_per_sec=12.0, trials=5,
    )
    path = entry.save(str(tmp_path))
    assert os.path.exists(path)
    again = tune.load_tuned("raft", cfg, 40, dir=str(tmp_path))
    assert again == entry
    # lane bucket 33..64 all resolve to the same entry
    assert tune.load_tuned("raft", cfg, 64, dir=str(tmp_path)) == entry
    # clean misses: other bucket, other workload, other config
    assert tune.load_tuned("raft", cfg, 128, dir=str(tmp_path)) is None
    assert tune.load_tuned("kv", cfg, 40, dir=str(tmp_path)) is None
    other = dataclasses.replace(cfg, horizon_us=123_456)
    assert tune.load_tuned("raft", other, 40, dir=str(tmp_path)) is None
    # resolve_tuning("auto") consumes the hit and survives the miss
    assert tune.resolve_tuning(
        "auto", "raft", cfg, 40, dir=str(tmp_path)
    ) == {"chunk": 32, "pipeline": False}
    assert tune.resolve_tuning(
        "auto", "raft", cfg, 128, dir=str(tmp_path)
    ) == {}


def test_tuned_cache_rejects_stale_format_and_wrong_device(tmp_path):
    from madsim_tpu.tpu.spec import SimConfig

    cfg = SimConfig()
    entry = tune.TunedEntry(
        device_kind=tune.device_kind(), workload="raft",
        config_hash=tune.config_hash_sans_tier_b(cfg),
        lane_bucket=tune.lane_bucket(40),
    )
    path = entry.save(str(tmp_path))

    def rewrite(**patch):
        doc = entry.to_doc()
        doc.update(patch)
        with open(path, "w") as f:
            json.dump(doc, f)

    # stale format version: loud reject, never silently reinterpreted
    rewrite(format="madsim-tpu-tuned/0")
    with pytest.raises(tune.TunedCacheError, match="format"):
        tune.load_tuned("raft", cfg, 40, dir=str(tmp_path))
    # wrong device_kind at the right key path (a cache copied from
    # another machine): loud reject
    rewrite(device_kind="TPU_v99")
    with pytest.raises(tune.TunedCacheError, match="does not match"):
        tune.load_tuned("raft", cfg, 40, dir=str(tmp_path))
    # unknown fields (written by a newer tree): loud reject
    rewrite(frobnicate=1)
    with pytest.raises(tune.TunedCacheError, match="unknown"):
        tune.load_tuned("raft", cfg, 40, dir=str(tmp_path))
    # a Tier-B knob smuggled into the dispatch dict: loud reject
    rewrite(dispatch={"msg_capacity": 8})
    with pytest.raises(tune.TunedCacheError, match="non-Tier-A"):
        tune.load_tuned("raft", cfg, 40, dir=str(tmp_path))


def test_resolve_tuning_forms():
    from madsim_tpu.tpu.spec import SimConfig

    cfg = SimConfig()
    assert tune.resolve_tuning(None, "raft", cfg, 64) == {}
    assert tune.resolve_tuning({"chunk": 8}, "raft", cfg, 64) == {"chunk": 8}
    with pytest.raises(ValueError, match="not Tier-A"):
        tune.resolve_tuning({"msg_capacity": 8}, "raft", cfg, 64)
    with pytest.raises(TypeError):
        tune.resolve_tuning(3.14, "raft", cfg, 64)


# -------------------------------------------------- Tier-A invariance matrix


@pytest.mark.chaos
def test_tier_a_invariance_matrix_run_batch():
    """Tuned dispatch knobs vs defaults on the chunked, pipelined,
    refill and sharded paths: per-seed rows bit-identical — the contract
    that makes Tier A safe to apply anywhere."""
    from madsim_tpu.tpu.batch import run_batch

    wl = _raft_workload()
    base = run_batch(range(48), wl, mesh=None, max_traces=0)
    for tuning in (
        {"chunk": 16, "pipeline": False},
        {"dispatch_steps": 200},
        {"refill_lanes": 8},
        {"chunk": 12, "dispatch_steps": 500, "refill_lanes": 4,
         "pipeline": False},
    ):
        got = run_batch(
            range(48), wl, mesh=None, max_traces=0, tuning=tuning
        )
        assert np.array_equal(base.violated, got.violated), tuning
        assert np.array_equal(base.deadlocked, got.deadlocked), tuning
        assert np.array_equal(
            base.violation_step, got.violation_step
        ), tuning
    # sharded legs (the suite conftest forces an 8-device CPU mesh): a
    # tuned `devices` entry must not move a row either, chunked and
    # refill paths both — mesh omitted so the tuned mesh actually lands
    for tuning in ({"devices": 2}, {"devices": 2, "refill_lanes": 8}):
        got = run_batch(range(48), wl, max_traces=0, tuning=tuning)
        assert got.summary.get("n_devices") == 2, tuning
        assert np.array_equal(base.violated, got.violated), tuning
        assert np.array_equal(base.deadlocked, got.deadlocked), tuning
        assert np.array_equal(
            base.violation_step, got.violation_step
        ), tuning


@pytest.mark.chaos
def test_tier_a_invariance_matrix_spread_mix():
    """The refill engine's own matrix on the 10x horizon-spread mix:
    lane width x segment length never moves a per-admission row."""
    from madsim_tpu.tpu.engine import refill_results

    sim, horizon = tune.spread_mix_sim(0.3)
    A = 24
    ctl = tune.spread_ctl_rows(horizon, A)
    seeds = np.arange(A, dtype=np.uint32)
    rows = []
    for lanes, dsteps in ((4, 10_000), (8, 10_000), (4, 64), (12, 500)):
        st = sim.run_refill(
            seeds, lanes=lanes, max_steps=20_000, dispatch_steps=dsteps,
            ctl=ctl,
        )
        res = refill_results(st)
        rows.append({
            k: np.asarray(res[k])
            for k in ("violated", "steps", "violation_step", "events")
        })
    for other in rows[1:]:
        for k, v in rows[0].items():
            assert np.array_equal(v, other[k]), k


def test_run_batch_rejects_mismatched_prebuilt_sim():
    """run_batch(sim=...) amortizes compiles for the SAME program only: a
    sim built for another (spec, config) would fuzz a different program
    under this workload's name — loud reject, never silent."""
    from madsim_tpu.tpu.batch import run_batch
    from madsim_tpu.tpu.engine import BatchedSim

    wl = _raft_workload()
    other_cfg = dataclasses.replace(wl.config, horizon_us=123_456)
    sim = BatchedSim(wl.spec, other_cfg)
    with pytest.raises(ValueError, match="different"):
        run_batch(range(8), wl, mesh=None, max_traces=0, sim=sim)


def test_run_batch_tuning_applies_and_explicit_args_win():
    from madsim_tpu.tpu.batch import run_batch

    wl = _raft_workload()
    tuned = run_batch(
        range(24), wl, mesh=None, max_traces=0,
        tuning={"refill_lanes": 8},
    )
    assert tuned.summary.get("refill_lanes") == 8
    # an explicit refill= beats the tuned value
    explicit = run_batch(
        range(24), wl, mesh=None, max_traces=0, refill=4,
        tuning={"refill_lanes": 8},
    )
    assert explicit.summary.get("refill_lanes") == 4
    # an explicit refill=0 pins the CHUNKED path (and its summary
    # schema) even when the cache holds a refill width — refill's
    # sentinel is None-omitted, so 0 is an explicit argument like any
    # other and the tuned value must not flip the path
    chunked = run_batch(
        range(24), wl, mesh=None, max_traces=0, refill=0,
        tuning={"refill_lanes": 8},
    )
    assert "refill_lanes" not in chunked.summary


def test_run_batch_cached_devices_beyond_host_falls_back():
    """A tuned entry recorded on a bigger host of the same device kind
    (the cache is keyed by KIND, not count) may name more devices than
    this host has. Applying it must degrade to the production default
    mesh — a cache entry is a throughput decision, never a crash."""
    import jax

    from madsim_tpu.tpu.batch import run_batch

    wl = _raft_workload()
    too_many = len(jax.devices()) + 7
    res = run_batch(
        range(16), wl, max_traces=0, tuning={"devices": too_many}
    )
    assert res.seeds.size == 16
    # the tuner's own search keeps the loud reject: there a bad count
    # is a caller bug, not a stale cache
    with pytest.raises(ValueError, match="visible"):
        tune._mesh_for(too_many)
    assert tune._mesh_for(too_many, cached=True) == "auto"


def test_explorer_tuning_applies_dispatch_knobs_and_explicit_wins():
    """The Explorer consumes every Tier-A knob it can honor — chunk,
    refill lane width, dispatch_steps, pipeline — with the same
    omitted-arg sentinel rule as run_batch (a cached `devices` stays
    unconsumed: island topology belongs to the Federation)."""
    from madsim_tpu.explore import Explorer
    from madsim_tpu.tpu.engine import DEFAULT_DISPATCH_STEPS

    wl = _raft_workload()
    tn = {"dispatch_steps": 123, "pipeline": False, "chunk": 8,
          "refill_lanes": 4}
    ex = Explorer(wl, lanes=16, tuning=tn)
    assert ex.dispatch_steps == 123
    assert ex.pipeline is False
    assert ex.chunk == 8
    assert ex.refill_lanes == 4
    # explicit arguments win over every tuned value
    ex2 = Explorer(
        wl, lanes=16, chunk=16, refill_lanes=8, dispatch_steps=456,
        pipeline=True, tuning=tn, sim=ex.sim,
    )
    assert ex2.dispatch_steps == 456
    assert ex2.pipeline is True
    assert ex2.chunk == 16
    assert ex2.refill_lanes == 8
    # untuned default: the engine's own segment length
    ex3 = Explorer(wl, lanes=16, sim=ex.sim)
    assert ex3.dispatch_steps == DEFAULT_DISPATCH_STEPS


def test_tier_a_devices_grid_excludes_auto_twin():
    """devices=0 already means a mesh over ALL visible devices, so the
    grid must not also list D — the twin would measure one configuration
    twice and a noise win could cache a phantom devices=D 'winner' that
    equals the default."""
    import jax

    wl = _raft_workload()
    ks = {k.name: k for k in tune.tier_a_knobs(wl, n_seeds=32)}
    D = len(jax.devices())
    if D > 1:
        vals = ks["devices"].values
        assert 0 in vals and D not in vals


def test_tune_workload_buckets_by_measured_scale():
    """The cache key's lane bucket is the MEASURED sweep size, not the
    requested lane count: knobs do not transfer across scale, so a
    `--lanes 4096 --seeds 8` run must write under l8 where only an
    8-seed consumer resolves it — never under l4096."""
    wl = _raft_workload(0.2)
    entry = tune.tune_workload(
        wl, "raft", lanes=4_096, n_seeds=8, knobs=(), save=False,
        guard_rounds=1,
    )
    assert entry.lane_bucket == tune.lane_bucket(8)


def test_tier_b_grids_center_on_engine_effective_depth():
    """Tier-B candidates are centered on the depths the engine actually
    derives for the default config (msg_depth_msg=None => capacity//C),
    and tier_b_effective_defaults names that value — so an
    effective-equal candidate is recognizable as the default program and
    can never be cached as a hash-moving no-op 'win'."""
    from madsim_tpu.tpu.engine import BatchedSim

    wl = _raft_workload()
    sim0 = BatchedSim(wl.spec, wl.config)
    ks = {k.name: k for k in tune.tier_b_config_knobs(wl)}
    assert int(sim0._Km) in ks["msg_depth_msg"].values
    eff = tune.tier_b_effective_defaults(wl, {"msg_depth_msg": None})
    assert eff["msg_depth_msg"] == int(sim0._Km)


# --------------------------------------------------------------- Tier-B gate


@pytest.mark.chaos
def test_tier_b_gate_rejects_planted_dropping_config():
    """The planted drop-inducing pool depth next to its clean twin: the
    gate's overflow leg must fire on the squeezed budget and stay quiet
    on the shipped one (which also re-earns its range certificate)."""
    wl = _raft_workload()
    clean = tune.tier_b_gate(wl, wl.config, seeds=48, certify=True)
    assert clean["ok"], clean["reasons"]
    planted = dataclasses.replace(
        wl.config, msg_capacity=8, msg_depth_msg=None
    )
    bad = tune.tier_b_gate(wl, planted, seeds=48, certify=False)
    assert not bad["ok"]
    assert any("overflow" in r for r in bad["reasons"])


def test_tier_b_gate_rejects_engine_refused_config():
    """Leg 1: a config the BatchedSim constructor refuses (here the
    narrow-horizon derating family of validations) is a gate reject with
    the constructor's own message, not a crash."""
    wl = _raft_workload()
    bad = dataclasses.replace(wl.config, msg_spare_slots=-1)
    gate = tune.tier_b_gate(wl, bad, seeds=8, certify=False)
    assert not gate["ok"]
    assert any("engine rejects" in r for r in gate["reasons"])


def test_apply_tier_b_requires_certification():
    from madsim_tpu.tpu.spec import SimConfig

    cfg = SimConfig()
    entry = tune.TunedEntry(
        device_kind="cpu", workload="raft", config_hash="x",
        lane_bucket=64, config={"msg_spare_slots": 2}, certified=False,
    )
    with pytest.raises(ValueError, match="certified"):
        tune.apply_tier_b(cfg, entry)
    entry.certified = True
    out = tune.apply_tier_b(cfg, entry)
    assert out.msg_spare_slots == 2
    assert out.hash() != cfg.hash()  # Tier B moves the config identity


# ------------------------------------------------------- search machinery


def test_coordinate_descent_picks_fast_value_and_guard_falls_back():
    """Pure-host search check: a deterministic fake clock makes value 7
    fastest; the descent must find it, and the A/B guard must keep the
    default when the 'tuned' assignment measures slower."""
    walls = {1: 0.9, 4: 0.5, 7: 0.2}

    def fake_measure(assign, rep):
        return walls[assign["k"]]

    tl = tune.TrialLog()
    best = tune.coordinate_descent(
        (tune.Knob("k", (1, 4, 7)),), fake_measure, {"k": 1}, tl
    )
    assert best == {"k": 7}
    assert all(t["knob"] in ("k",) for t in tl.trials)

    meds = tune.ab_guard(
        lambda a, rep: 1.0 if a["k"] == 7 else 0.5,  # tuned slower now
        {"k": 1}, {"k": 7}, tl,
    )
    assert meds["tuned"] >= meds["default"]  # caller falls back


def test_guard_tier_a_falls_back_and_accounts():
    """The hoisted never-regress guard: a losing assignment is replaced
    by the default, and the seeds/s accounting reflects the default."""
    tl = tune.TrialLog()
    best, fallback, base_sps, tuned_sps = tune._guard_tier_a(
        lambda a, rep: 1.0 if a["k"] == 7 else 0.5,
        {"k": 1}, {"k": 7}, tl, work_items=10, guard_rounds=1,
    )
    assert fallback and best == {"k": 1}
    assert base_sps == tuned_sps == 10 / 0.5


def test_tier_b_measured_under_post_guard_tier_a(monkeypatch):
    """Ordering regression: the Tier-A never-regress guard runs BEFORE
    the Tier-B pass, so Tier-B candidates are measured (and certified)
    under the dispatch shape the entry actually ships. Guarding after
    would let the guard discard the assignment the Tier-B win was
    measured under — a cached entry that can be a slowdown."""
    wl = _raft_workload(0.2)
    doctored = {}

    def fake_descent(knobs, measure, default, tl):
        doctored.update(default, chunk=2)  # a "winner" the guard rejects
        return dict(doctored)

    def fake_ab_guard(measure, default, best, tl, rounds=2):
        return {"default": 0.5, "tuned": 1.0}  # tuned measures slower

    seen = {}

    def spy_tier_b(workload, tier_a, n_seeds, tl, **kw):
        seen["tier_a"] = dict(tier_a)
        return {}, {}, False

    monkeypatch.setattr(tune, "coordinate_descent", fake_descent)
    monkeypatch.setattr(tune, "ab_guard", fake_ab_guard)
    monkeypatch.setattr(tune, "_tune_tier_b", spy_tier_b)
    entry = tune.tune_workload(
        wl, "raft", lanes=8, n_seeds=8, tier="AB", save=False
    )
    # the Tier-B pass saw the POST-guard (default) assignment, not the
    # discarded descent winner
    assert seen["tier_a"]["chunk"] == 8
    assert seen["tier_a"] != doctored
    assert entry.fallback and entry.dispatch == {}


def test_campaign_tuning_applies_pipeline(tmp_path):
    """Campaign leaves `pipeline` on the Explorer's None sentinel so a
    tuned pipeline knob actually lands (a silently-unapplied knob next
    to a checkpoint that claims it was applied is the r10 dropped-mesh
    class); the checkpoint's explorer_params record the APPLIED value,
    which resume replays explicitly."""
    from madsim_tpu.campaign import Campaign, explorer_params

    wl = _raft_workload(0.2)
    c = Campaign(
        wl, str(tmp_path / "c1"), lanes=8, tuning={"pipeline": False}
    )
    assert c.ex.pipeline is False
    assert explorer_params(c.ex)["pipeline"] is False
    # an explicit argument still wins over the tuned dict
    c2 = Campaign(
        wl, str(tmp_path / "c2"), lanes=8, sim=c.ex.sim,
        tuning={"pipeline": False}, pipeline=True,
    )
    assert c2.ex.pipeline is True


def test_trial_log_routes_through_metrics_registry(tmp_path):
    """Satellite: tuning trials ride the r11 metrics registry — a
    per-knob trial counter, the measured-ms histogram, and a span per
    trial on the wall-clock timeline."""
    from madsim_tpu import telemetry

    telemetry.enable(out_dir=str(tmp_path))
    try:
        tl = tune.TrialLog()
        tl.trial(lambda a, rep: 0.01, {"k": 1}, "refill_lanes", 1)
        tl.trial(lambda a, rep: 0.02, {"k": 2}, "refill_lanes", 2)
        reg = telemetry.get_registry()
        assert reg.counter("tune_trials_total").value(
            knob="refill_lanes"
        ) == 2
        snap = reg.histogram("tune_trial_ms").snapshot(knob="refill_lanes")
        assert snap and snap["count"] == 2
        assert any(s.name == "tune_trial" for s in telemetry.spans())
    finally:
        telemetry.disable()


@pytest.mark.chaos
def test_tune_workload_writes_the_key_consumers_resolve(tmp_path):
    """THE silent-no-op regression: the cache identity is the SPEC name
    ("raft5"), because that is what every tuning="auto" consumer
    (run_batch, Campaign, Explorer, ttfb, shrink_seed) resolves with —
    an entry written under the registry/CLI name ("raft") would never be
    found and auto-tuning would silently run defaults everywhere."""
    wl = _raft_workload(0.2)
    entry = tune.tune_workload(
        wl, "raft", lanes=8, n_seeds=8, knobs=(),
        cache_dir=str(tmp_path), save=True, guard_rounds=1,
    )
    assert entry.workload == wl.spec.name == "raft5"
    cfg = wl.config
    assert tune.load_tuned(
        wl.spec.name, cfg, 8, dir=str(tmp_path)
    ) == entry
    # and the consumer-side resolve path sees it
    assert tune.resolve_tuning(
        "auto", wl.spec.name, cfg, 8, dir=str(tmp_path)
    ) == entry.dispatch


# ------------------------------------------------ campaign resume conflicts


def test_check_resume_conflicts_on_tuning():
    from madsim_tpu.campaign import check_resume_conflicts

    man = {
        "params": {"meta_seed": 0, "lanes": 16, "chunk": 16},
        "workload": {"name": "raft", "virtual_secs": 1.0},
        "tuning": {"chunk": 64, "refill_lanes": 8},
    }
    # same tuning: fine; omitted: defers to the checkpoint
    check_resume_conflicts(man, {"tuning": {"chunk": 64, "refill_lanes": 8}})
    check_resume_conflicts(man, {})
    # a DIFFERENT tuned dict (another tuned cache): loud reject
    with pytest.raises(ValueError, match="tuning"):
        check_resume_conflicts(man, {"tuning": {"chunk": 32}})
    # checkpoint tuned, request pinning defaults: loud reject too
    with pytest.raises(ValueError, match="tuning"):
        check_resume_conflicts(man, {"tuning": None})
    # untuned checkpoint accepts only untuned pins
    man2 = dict(man, tuning=None)
    check_resume_conflicts(man2, {"tuning": None})
    with pytest.raises(ValueError, match="tuning"):
        check_resume_conflicts(man2, {"tuning": {"chunk": 64}})


def test_serve_request_auto_tuning_resolves_before_conflict_check(
    tmp_path, monkeypatch,
):
    """A service request with "tuning": "auto" must RESUME cleanly while
    the tuned cache is unchanged: the raw string resolves against the
    checkpoint's own workload + lane scale BEFORE the conflict check, so
    the comparison is resolved-vs-resolved, never "auto" vs a dict."""
    from madsim_tpu.campaign import (
        _explicit_request_params, check_resume_conflicts,
        named_workload_ref,
    )
    from madsim_tpu.explore import _named_workload

    monkeypatch.setenv("MADSIM_TUNED_DIR", str(tmp_path))
    man = {
        "workload": named_workload_ref("raft", 0.5, False),
        "params": {"meta_seed": 0, "lanes": 16, "chunk": 16},
        "tuning": None,
    }
    # clean cache miss: "auto" resolves to None == the checkpoint's None
    given = _explicit_request_params({"tuning": "auto"}, man)
    assert given["tuning"] is None
    check_resume_conflicts(man, given)
    # cache populated with the SAME dict the checkpoint persisted:
    # restart with "auto" still resumes
    wl = _named_workload("raft", 0.5, False)
    tune.TunedEntry(
        device_kind=tune.device_kind(), workload=wl.spec.name,
        config_hash=tune.config_hash_sans_tier_b(wl.config),
        lane_bucket=tune.lane_bucket(16),
        dispatch={"chunk": 8},
    ).save(str(tmp_path))
    man2 = dict(man, tuning={"chunk": 8})
    given2 = _explicit_request_params({"tuning": "auto"}, man2)
    assert given2["tuning"] == {"chunk": 8}
    check_resume_conflicts(man2, given2)
    # a re-tuned cache (different dict) against the old checkpoint: loud
    with pytest.raises(ValueError, match="tuning"):
        check_resume_conflicts(man, given2)


@pytest.mark.chaos
def test_campaign_persists_tuning_and_rejects_resume_drift(tmp_path):
    """The checkpoint persists the RESOLVED tuning; resume replays it
    (never re-tunes) and a resume under a different tuned dict is a loud
    reject — the r10 'silently dropped mesh' bug class."""
    from madsim_tpu.campaign import Campaign

    from tests.test_explore import _planted_workload

    wl = _planted_workload()
    c = Campaign(
        wl, str(tmp_path / "c1"), meta_seed=3, lanes=8,
        shrink=False, tuning={"chunk": 4, "refill_lanes": 4},
    )
    assert c.tuning == {"chunk": 4, "refill_lanes": 4}
    assert c.ex.chunk == 4 and c.ex.refill_lanes == 4
    c.checkpoint()
    with open(tmp_path / "c1" / "manifest.json") as f:
        man = json.load(f)
    assert man["tuning"] == {"chunk": 4, "refill_lanes": 4}
    # resume without tuning= replays the persisted tuning verbatim
    c2 = Campaign.resume(str(tmp_path / "c1"), workload=wl)
    assert c2.tuning == {"chunk": 4, "refill_lanes": 4}
    assert c2.ex.chunk == 4 and c2.ex.refill_lanes == 4
    # resume under a different tuned cache: loud reject
    with pytest.raises(ValueError, match="tuning"):
        Campaign.resume(
            str(tmp_path / "c1"), workload=wl, tuning={"chunk": 8}
        )
    # resume under the SAME tuning: fine
    c3 = Campaign.resume(
        str(tmp_path / "c1"), workload=wl,
        tuning={"chunk": 4, "refill_lanes": 4},
    )
    assert c3.tuning == c.tuning


# ------------------------------------------------------------ shrink wiring


def test_shrink_seed_accepts_tuning_lane_width():
    """triage.shrink_seed(tuning=...) adopts the tuned refill lane width
    only where the caller kept the default (signature-level check: the
    resolve path runs and an explicit width still wins)."""
    import inspect

    from madsim_tpu import triage

    sig = inspect.signature(triage.shrink_seed)
    assert "tuning" in sig.parameters
