"""The static verifier verified: every rule fires on its planted fixture
and passes on the shipped tree (ISSUE 8).

Layer-1 rules are exercised twice: on deliberately broken toy step
programs under tests/fixtures/analysis/ (the rule FIRES) and on the real
raft step program traced abstractly (the rule passes) — the jaxpr smoke
reuses one small fixed lane width so the whole module stays seconds-fast
(tracing only; nothing compiles, nothing touches a device). Layer-2
source rules run against planted source fixtures and the live tree."""

import importlib.util
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from madsim_tpu import analysis
from madsim_tpu.analysis import lint
from madsim_tpu.analysis.jaxpr_check import (
    LANES,
    check_callbacks,
    check_dtype,
    check_lane_independence,
    check_rng_taint,
    check_run_carry,
    check_step_donation,
    verify_workload,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")


def _load_toys():
    spec = importlib.util.spec_from_file_location(
        "analysis_toy_steps", os.path.join(FIXTURES, "toy_steps.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


toys = _load_toys()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ----------------------------------------------------------- rule: callbacks


def test_callbacks_rule_fires_on_planted_callback():
    closed = jax.make_jaxpr(toys.callback_step)(_sds((LANES,), jnp.float32))
    res = check_callbacks(closed, "toy")
    assert not res.ok
    assert any("debug" in v.detail for v in res.violations)


def test_callbacks_rule_passes_clean():
    closed = jax.make_jaxpr(toys.clean_step)(_sds((LANES,), jnp.float32))
    assert check_callbacks(closed, "toy").ok


# ----------------------------------------------------------- rule: rng-taint


def test_rng_taint_fires_on_trajectory_coupled_schedule_draw():
    closed = jax.make_jaxpr(toys.impure_schedule_draw)(
        _sds((LANES,), jnp.uint32), _sds((LANES,), jnp.int32)
    )
    res = check_rng_taint(
        closed, ["const.key0", "hot.clock"], {"hot.clock"}, "toy"
    )
    assert not res.ok
    assert any("schedule-purity" in v.detail for v in res.violations)
    assert any("hot.clock" in v.detail for v in res.violations)


def test_rng_taint_witness_survives_inline_jit():
    """The mix eqns live inside a jit sub-jaxpr; the violation must
    still fire AND name the offending leaf via the enclosing top-level
    equation."""
    closed = jax.make_jaxpr(toys.impure_draw_inside_jit)(
        _sds((LANES,), jnp.uint32), _sds((LANES,), jnp.int32)
    )
    res = check_rng_taint(
        closed, ["const.key0", "hot.clock"], {"hot.clock"}, "toy"
    )
    assert not res.ok
    assert any("hot.clock" in v.detail for v in res.violations), [
        v.render() for v in res.violations
    ]


def test_rng_taint_passes_occurrence_indexed_draw():
    closed = jax.make_jaxpr(toys.pure_schedule_draw)(
        _sds((LANES,), jnp.uint32), _sds((LANES,), jnp.int32)
    )
    res = check_rng_taint(
        closed, ["const.key0", "hot.nem.crash_k"], set(), "toy"
    )
    assert res.ok, [v.render() for v in res.violations]
    assert res.checked > 0  # the mixes were actually examined


def test_rng_taint_fires_on_contaminated_funnel():
    closed = jax.make_jaxpr(toys.contaminated_funnel)(
        _sds((LANES,), jnp.uint32), _sds((LANES, 3), jnp.int32)
    )
    res = check_rng_taint(
        closed, ["hot.key", "hot.msgs.payload"], set(), "toy",
        key_out_index=0,
    )
    assert not res.ok
    assert any("funnel" in v.detail for v in res.violations)


def test_rng_taint_passes_clean_funnel():
    closed = jax.make_jaxpr(toys.clean_funnel)(
        _sds((LANES,), jnp.uint32), _sds((LANES, 3), jnp.int32)
    )
    res = check_rng_taint(
        closed, ["hot.key", "hot.msgs.payload"], set(), "toy",
        key_out_index=0,
    )
    assert res.ok, [v.render() for v in res.violations]


# the refill toy signature: (key, key0, done, qseeds, cursor) — seeds are
# key ROOTS (the _init verification convention), the cursor is a neutral
# admission input, and `done` is a bool whose taint the control boundary
# strips
_REFILL_TOY_NAMES = [
    "hot.key", "hot.key0", "hot.done", "const.key0",
    "cold.refill.cursor",
]


def _refill_toy_args():
    return (
        _sds((LANES,), jnp.uint32), _sds((LANES,), jnp.uint32),
        _sds((LANES,), jnp.bool_), _sds((29,), jnp.uint32),
        _sds((), jnp.int32),
    )


def test_rng_taint_fires_on_leaky_refill():
    """The planted continuous-batching leak: a refilled lane's init
    folds a SURVIVOR'S running key chain into its new schedule root —
    its fault schedule then depends on how far other admissions happened
    to have run. rng-taint must flag the key0-rooted draw mixing chain
    (KEY2) material."""
    closed = jax.make_jaxpr(toys.leaky_refill)(*_refill_toy_args())
    res = check_rng_taint(closed, _REFILL_TOY_NAMES, set(), "toy")
    assert not res.ok
    assert any("schedule-purity" in v.detail for v in res.violations)


def test_rng_taint_passes_clean_refill():
    """The legal refill twin: new chain roots derive from the admitted
    queue seed alone (exactly a fresh lane's _init draw); the
    retirement mask is control, not value material."""
    closed = jax.make_jaxpr(toys.clean_refill)(*_refill_toy_args())
    res = check_rng_taint(closed, _REFILL_TOY_NAMES, set(), "toy")
    assert res.ok, [v.render() for v in res.violations]
    assert res.checked > 0


# the device-loop toy signature: (key, meta_key, counter, ring_seed,
# ring_n, done) — the ring's seed column is a key ROOT (the same _init
# verification convention as the refill queue's seed column), the
# MetaRng cursor and ring row count are neutral schedule-root inputs
# (jaxpr_check.DEVLOOP_NEUTRAL), and `done` is control material
_DEVLOOP_TOY_NAMES = [
    "hot.key", "cold.loop.meta_key", "cold.loop.counter", "const.key0",
    "cold.loop.ring_n", "hot.done",
]


def _devloop_toy_args():
    return (
        _sds((LANES,), jnp.uint32), _sds((), jnp.uint32),
        _sds((), jnp.int32), _sds((7,), jnp.uint32),
        _sds((), jnp.int32), _sds((LANES,), jnp.bool_),
    )


def test_rng_taint_fires_on_leaky_ring():
    """The planted device-loop leak (r19): the corpus-ring scatter folds
    a SURVIVOR LANE'S running key chain into a stored seed — every
    mutant descended from that ring row then runs a fault schedule that
    depends on how far other lanes happened to have run. rng-taint must
    flag the ring-rooted draw mixing chain (KEY2) material."""
    closed = jax.make_jaxpr(toys.leaky_ring)(*_devloop_toy_args())
    res = check_rng_taint(closed, _DEVLOOP_TOY_NAMES, set(), "toy")
    assert not res.ok
    assert any("schedule-purity" in v.detail for v in res.violations)


def test_rng_taint_passes_clean_devloop_ring():
    """The legal twin: the mutant root derives from the ring parent's
    seed alone, picked by a MetaRng draw off the (neutral) meta cursor —
    survivors' chains never reach the ring."""
    closed = jax.make_jaxpr(toys.clean_devloop_ring)(*_devloop_toy_args())
    res = check_rng_taint(closed, _DEVLOOP_TOY_NAMES, set(), "toy")
    assert res.ok, [v.render() for v in res.violations]
    assert res.checked > 0


def _toy_mesh():
    import numpy as np

    return jax.sharding.Mesh(np.array(jax.devices()[:2]), ("devices",))


def test_collective_walk_fires_on_planted_psum():
    """The planted multi-chip leak: a psum inside the shard_map'd
    segment couples every device's rows to every other's — the
    lane-independence rule's collective walk must name the exact
    primitive."""
    from madsim_tpu.analysis.jaxpr_check import check_collectives

    mesh = _toy_mesh()
    x = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    closed = jax.make_jaxpr(toys.leaky_sharded_segment(mesh))(x)
    res = check_collectives(closed, "toy")
    assert not res.ok
    assert any("psum" in v.detail for v in res.violations)
    assert res.rule == "lane-independence"


def test_collective_walk_passes_clean_sharded_segment():
    """The legal twin: per-device compute only — zero collectives. An
    exact-primitive allowlist entry (never wholesale) would also pass
    the planted psum, pinned here so the allowlist stays exact."""
    from madsim_tpu.analysis.jaxpr_check import check_collectives

    mesh = _toy_mesh()
    x = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    closed = jax.make_jaxpr(toys.clean_sharded_segment(mesh))(x)
    res = check_collectives(closed, "toy")
    assert res.ok, [v.render() for v in res.violations]
    assert res.checked > 0
    leaky = jax.make_jaxpr(toys.leaky_sharded_segment(mesh))(x)
    allowed = check_collectives(leaky, "toy", allow=("psum",))
    assert allowed.ok  # exact-name allowlist is honored, nothing broader


# --------------------------------------------------------------- rule: dtype


def _fake_sim(narrow=None, time_fields=()):
    return SimpleNamespace(
        spec=SimpleNamespace(
            narrow_fields=narrow or {}, time_fields=tuple(time_fields)
        )
    )


def test_dtype_rule_fires_on_float_time_arithmetic():
    closed = jax.make_jaxpr(toys.time_f32_step)(_sds((LANES,), jnp.int32))
    res = check_dtype(
        closed, _fake_sim(), None, (None,), ["hot.timer"], "toy"
    )
    assert not res.ok
    assert any("float arithmetic" in v.detail for v in res.violations)


def test_dtype_rule_passes_integer_ppm_time_math():
    closed = jax.make_jaxpr(toys.time_int_step)(_sds((LANES,), jnp.int32))
    res = check_dtype(
        closed, _fake_sim(), None, (None,), ["hot.timer"], "toy"
    )
    assert res.ok, [v.render() for v in res.violations]


def test_dtype_rule_fires_on_widened_narrow_field():
    closed = jax.make_jaxpr(toys.clean_step)(_sds((LANES,), jnp.float32))
    hot = SimpleNamespace(
        node=SimpleNamespace(term=_sds((LANES, 5), jnp.uint16))
    )
    out = (
        SimpleNamespace(node=SimpleNamespace(term=_sds((LANES, 5), jnp.int32))),
    )
    res = check_dtype(
        closed, _fake_sim(narrow={"term": jnp.uint16}), hot, out,
        ["hot.x"], "toy",
    )
    assert not res.ok
    assert any("silently widened" in v.detail for v in res.violations)


# --------------------------------------------------- rule: lane-independence


def test_lane_rule_fires_on_cross_lane_reduction():
    closed = jax.make_jaxpr(toys.lane_coupled_step)(
        _sds((LANES, 5), jnp.float32)
    )
    res = check_lane_independence(closed, LANES, "toy")
    assert not res.ok
    assert any("cross-lane" in v.detail for v in res.violations)


def test_lane_rule_fires_on_rhs_and_transposed_contractions():
    # a lane contraction hides on the RHS operand of a matmul ...
    closed = jax.make_jaxpr(toys.lane_coupled_rhs_matmul)(
        _sds((5, LANES), jnp.float32), _sds((LANES, 5), jnp.float32)
    )
    assert not check_lane_independence(closed, LANES, "toy").ok
    # ... or behind a transpose that moves the lane axis off position 0
    closed = jax.make_jaxpr(toys.lane_coupled_transposed)(
        _sds((LANES, 5), jnp.float32)
    )
    assert not check_lane_independence(closed, LANES, "toy").ok


def test_lane_rule_passes_lane_local_reduction():
    closed = jax.make_jaxpr(toys.lane_local_step)(
        _sds((LANES, 5), jnp.float32)
    )
    assert check_lane_independence(closed, LANES, "toy").ok


# ------------------------------------------------------------ rule: donation


def test_donation_rule_fires_on_undonatable_carry_leaf():
    hot, cold, const = toys.toy_state()
    res = check_step_donation(
        toys.widened_toy_step, hot, cold, const,
        toys.HOT_NAMES, toys.COLD_NAMES, toys.CONST_NAMES, "toy",
    )
    assert not res.ok
    # widening hot.x leaves ONE i32 carry leaf without a matching output
    # buffer; jax assigns the surviving alias greedily, so either i32
    # leaf may be the one reported — what matters is that a carry leaf
    # lost its donation
    assert any(
        "NOT donated" in v.detail
        and ("hot.x" in v.detail or "cold.acc" in v.detail)
        for v in res.violations
    )


def test_donation_rule_passes_clean_toy_step():
    hot, cold, const = toys.toy_state()
    res = check_step_donation(
        toys.good_toy_step, hot, cold, const,
        toys.HOT_NAMES, toys.COLD_NAMES, toys.CONST_NAMES, "toy",
    )
    assert res.ok, [v.render() for v in res.violations]


def test_donation_rule_fires_on_const_leaking_into_while_carry():
    hot, cold, const = toys.toy_state()
    closed = jax.make_jaxpr(toys.leaky_toy_run)(hot, cold, const)
    res = check_run_carry(closed, hot, cold, const, "toy")
    assert not res.ok
    assert any("carry" in v.detail for v in res.violations)


def test_donation_rule_passes_clean_while_carry():
    hot, cold, const = toys.toy_state()
    closed = jax.make_jaxpr(toys.good_toy_run)(hot, cold, const)
    res = check_run_carry(closed, hot, cold, const, "toy")
    assert res.ok, [v.render() for v in res.violations]


# ----------------------------------------------------- rule: ambient-entropy


def test_entropy_rule_fires_on_planted_fixture():
    res = lint.check_entropy_file(os.path.join(FIXTURES, "entropy_bad.py"))
    assert len(res.violations) == 7, [v.render() for v in res.violations]
    hits = " ".join(v.detail for v in res.violations)
    for needle in ("time.time", "random.random", "np.random.rand",
                   "os.urandom", "npr.rand", "default_rng", "date.today"):
        assert needle in hits
    # the pragma'd urandom and perf_counter were allowed
    assert sum("urandom" in v.detail for v in res.violations) == 1
    assert "perf_counter" not in hits


def test_entropy_rule_passes_shipped_tree():
    res = lint.check_entropy()
    assert res.ok, [v.render() for v in res.violations]
    assert res.checked > 1000  # it actually walked the package


# ---------------------------------------------------------- rule: both-faces


def test_both_faces_rule_fires_on_extra_device_fold():
    fix = os.path.join(FIXTURES, "cov_faces_bad.py")
    res = lint.check_both_faces(engine_path=fix, mirror_path=fix)
    assert not res.ok
    hits = " ".join(v.detail for v in res.violations)
    assert "5" in hits and "4" in hits  # device 5 folds vs mirror 4
    assert any("COV_FIELDS" in v.where or "COV_FIELDS" in v.detail
               for v in res.violations)


def test_both_faces_rule_fires_on_substituted_field():
    """Counts agree (4 == 4) but the device face folds payload_crc where
    the registry names bucket — the sequence check must fire."""
    fix = os.path.join(FIXTURES, "cov_faces_subst.py")
    res = lint.check_both_faces(engine_path=fix, mirror_path=fix)
    assert not res.ok
    assert any(
        "payload_crc" in v.detail and "bucket" in v.detail
        for v in res.violations
    ), [v.render() for v in res.violations]


def test_both_faces_rule_passes_shipped_tree():
    res = lint.check_both_faces()
    assert res.ok, [v.render() for v in res.violations]


# -------------------------------------------------------------- rule: mirror


def test_mirror_rule_fires_on_unhandled_event_kind():
    from madsim_tpu import nemesis as nem

    broken = dict(nem.CLAUSE_EVENT_KINDS)
    broken["spike"] = ("spike_on", "spike_off", "spike_pulse")
    res = lint.check_mirror(event_kinds=broken)
    assert not res.ok
    assert any("spike_pulse" in v.detail for v in res.violations)


def test_mirror_rule_fires_on_unregistered_clause():
    from madsim_tpu import nemesis as nem

    partial = {
        k: v for k, v in nem.SCHEDULE_CLAUSES.items() if k != "clog"
    }
    res = lint.check_mirror(schedule_clauses=partial)
    assert not res.ok
    assert any("LinkClog" in v.detail for v in res.violations)


def test_mirror_rule_ignores_docstring_prose():
    """A kind surviving only in a docstring after its handler was deleted
    must NOT count as handled."""
    fake_driver = '\n'.join([
        "class NemesisDriver:",
        "    def install(self):",
        '        """applies skew and spike_on windows at install"""',
        "    def _apply(self, ev):",
        "        for k in ('crash', 'restart', 'split', 'heal', 'clog',",
        "                  'unclog', 'spike_on', 'spike_off'):",
        "            if ev.kind == k:",
        "                return",
    ])
    res = lint.check_mirror(driver_source=fake_driver)
    assert any("skew" in v.detail and "never handles" in v.detail
               for v in res.violations), [v.render() for v in res.violations]


def test_mirror_rule_fires_on_driver_missing_reconfig_path():
    """The r17 fixture: a NemesisDriver whose _apply handles every legacy
    kind (and assigns skew) but never the reconfig clause's remove/join —
    the host application path of the membership axis silently gone. The
    mirror rule must name BOTH halves of the missing window."""
    fake_driver = '\n'.join([
        "class NemesisDriver:",
        "    def install(self):",
        "        self._assign('skew')",
        "    def _apply(self, ev):",
        "        for k in ('crash', 'restart', 'split', 'heal', 'clog',",
        "                  'unclog', 'spike_on', 'spike_off'):",
        "            if ev.kind == k:",
        "                return",
    ])
    res = lint.check_mirror(driver_source=fake_driver)
    assert not res.ok
    missing = [v for v in res.violations if "never handles" in v.detail]
    assert any("'remove'" in v.detail for v in missing), (
        [v.render() for v in res.violations]
    )
    assert any("'join'" in v.detail for v in missing)


def test_mirror_rule_fires_on_clause_without_host_coin_methods():
    """Face (f): a message clause with no HOST_COIN_METHODS entry is a
    FaultPlan clause whose host draws the oracle cannot verify."""
    from madsim_tpu import nemesis as nem

    partial = {
        k: v for k, v in nem.HOST_COIN_METHODS.items() if k != "reorder"
    }
    res = lint.check_mirror(host_coin_methods=partial)
    assert not res.ok
    assert any(
        "reorder" in v.detail and "not schedule-matched" in v.detail
        for v in res.violations
    ), [v.render() for v in res.violations]


def test_mirror_rule_fires_when_net_layer_never_draws():
    """Face (f): a registered draw method the net layer never calls means
    that clause's host face fell back to the ambient rng."""
    res = lint.check_mirror(net_source="x = 1\n")
    assert not res.ok
    assert any(
        "never called" in v.detail and "ambient rng" in v.detail
        for v in res.violations
    ), [v.render() for v in res.violations]


def test_mirror_rule_fires_when_oracle_ignores_the_registry():
    """Face (f): oracle.py must consume HOST_COIN_METHODS itself, or a
    new clause could ship on three faces without a comparator."""
    res = lint.check_mirror(oracle_source="pass\n")
    assert not res.ok
    assert any(
        "HOST_COIN_METHODS" in v.detail for v in res.violations
    ), [v.render() for v in res.violations]


def test_mirror_rule_fires_when_host_never_consumes_disk_coin():
    """Face (f), r18 half: `disk` is a SCHEDULE clause with a host coin
    (disk_torn_extent — the torn-tail extent FsSim keeps at a power
    fail). A driver+fs pair that handles every event kind but never
    touches the coin would silently UN-TEAR every scheduled torn crash
    on the host face; the mirror rule must catch that apply-path gap."""
    fake_driver = '\n'.join([
        "class NemesisDriver:",
        "    def install(self):",
        "        self._assign('skew')",
        "    def _apply(self, ev):",
        "        for k in ('crash', 'restart', 'split', 'heal', 'clog',",
        "                  'unclog', 'spike_on', 'spike_off', 'remove',",
        "                  'join', 'disk_slow', 'disk_crash',",
        "                  'disk_recover'):",
        "            if ev.kind == k:",
        "                return",
    ])
    res = lint.check_mirror(driver_source=fake_driver, fs_source="x = 1\n")
    assert not res.ok
    assert any(
        "disk_torn_extent" in v.detail and "un-tears" in v.detail
        for v in res.violations
    ), [v.render() for v in res.violations]


def test_mirror_rule_fires_on_stray_host_coin_entry():
    from madsim_tpu import nemesis as nem

    stray = dict(nem.HOST_COIN_METHODS)
    stray["jitter"] = ("loss",)
    res = lint.check_mirror(host_coin_methods=stray)
    assert not res.ok
    assert any("jitter" in v.detail for v in res.violations)


def test_mirror_rule_passes_shipped_registries():
    res = lint.check_mirror()
    assert res.ok, [v.render() for v in res.violations]


# ---------------------------------------------------- rule: layout-agreement


def test_layout_rule_fires_on_drifted_tables():
    res = lint.check_layout_agreement(
        narrow_fields={"bogus_field": jnp.uint8}
    )
    assert not res.ok
    assert any("bogus_field" in v.detail for v in res.violations)


def test_layout_rule_passes_shipped_tables():
    res = lint.check_layout_agreement()
    assert res.ok, [v.render() for v in res.violations]


# ------------------------------------------------------ rule: marker-hygiene


def test_marker_rule_fires_on_planted_unmarked_tests():
    res = lint.check_marker_hygiene_file(
        os.path.join(FIXTURES, "unmarked_slow_cases.py")
    )
    offenders = {v.detail.split()[0] for v in res.violations}
    assert offenders == {
        "test_soak_unmarked",
        "test_big_sweep_budgeted",
        # chaos does not exclude a test from the default run, so a
        # measured budget note still demands slow/deep
        "test_chaos_marked_but_budgeted",
    }, [v.render() for v in res.violations]


def test_marker_rule_passes_shipped_tests():
    res = lint.check_marker_hygiene()
    assert res.ok, [v.render() for v in res.violations]


# ------------------------------------------------- the real step program


def test_jaxpr_verifier_green_on_raft():
    """The foundation claim: the REAL raft step program (all nemesis
    clauses + triage + coverage, donated) satisfies every jaxpr rule.
    Abstract tracing only — the lane-width trick keeps this under a
    minute cold, seconds warm."""
    results = verify_workload("raft", log=None)
    bad = [v for r in results for v in r.violations]
    assert not bad, [v.render() for v in bad]
    by_rule = {r.rule for r in results}
    assert {"callbacks", "rng-taint", "dtype", "lane-independence",
            "donation"} <= by_rule
    # the rules saw real work: raft's step has >50 mix eqns and a
    # donated carry of dozens of leaves
    checked = {r.rule: 0 for r in results}
    for r in results:
        checked[r.rule] += r.checked
    assert checked["rng-taint"] > 50
    assert checked["donation"] > 30
    assert checked["lane-independence"] > 20


# ------------------------------------------------- shared traces + budget


def test_one_trace_per_workload_is_cached():
    """Perf satellite: every jaxpr rule (purity, taint, donation, dtype,
    lane, range) consumes ONE cached abstract trace per workload —
    re-requesting must return the same object, not re-trace."""
    from madsim_tpu.analysis.jaxpr_check import get_trace

    t1 = get_trace("raft", log=None)
    t2 = get_trace("raft", log=None)
    assert t1 is t2
    assert t1.closed_step is t2.closed_step
    assert len(t1.names) == len(t1.invars_avals)
    assert len(t1.out_names) == len(t1.closed_step.jaxpr.outvars)


@pytest.mark.slow
def test_full_analysis_all_stays_under_budget():
    """The --all acceptance bar: source lints + every jaxpr/range rule
    over all six trace targets (five workloads + raft's refill carry) in
    one process, sharing one trace per target, in well under 120 s on
    CPU (~45 s measured warm)."""
    import time

    t0 = time.perf_counter()
    summary = analysis.run_analysis(
        workloads=list(analysis.WORKLOADS), lint=True, log=None
    )
    wall = time.perf_counter() - t0
    assert summary["ok"] is True, summary["violation_details"]
    assert set(summary["certificates"]) == set(analysis.WORKLOADS) | {
        "_sum64"
    }
    assert wall < 120, f"--all took {wall:.0f}s (budget 120s)"


# ------------------------------------------------------------ summary + CLI


def test_summary_json_shape(tmp_path):
    summary = analysis.run_analysis(workloads=[], lint=True, log=None)
    assert summary["schema"] == analysis.SCHEMA
    assert summary["ok"] is True
    assert set(analysis.LINT_RULES) <= set(summary["rules"])
    for row in summary["rules"].values():
        assert row["status"] == "pass"
        assert row["violations"] == 0
    out = tmp_path / "analysis.json"
    analysis.write_summary(summary, str(out))
    assert json.loads(out.read_text())["ok"] is True


def test_empty_rule_set_is_not_a_pass():
    summary = analysis.run_analysis(workloads=[], lint=False, log=None)
    assert summary["ok"] is False  # zero rules ran: never green


def test_cli_lint_only_exits_zero(tmp_path):
    from madsim_tpu.analysis.__main__ import main

    out = tmp_path / "summary.json"
    rc = main(["--quiet", "--json", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["ok"] is True and doc["workloads"] == []


def test_cli_rejects_zero_rule_invocation():
    from madsim_tpu.analysis.__main__ import main

    with pytest.raises(SystemExit) as exc:
        main(["--no-lint"])
    assert exc.value.code == 2  # argparse usage error, not a green exit
