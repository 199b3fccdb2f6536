"""The lease5 configuration and its lease5.timer cell on the CPU: found by
name, a small sound run reads correct, each planted control and fault
reads not correct, the plain reference on the lanes' final states, and
the `step_membership_us` reader's map from the compiled programs."""

import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness, scopes, trace

CELL = "lease5.timer"
SWEEP = {"seeds_per_call": 64, "sample_lanes_per_chunk": 64}


@pytest.fixture(autouse=True)
def out_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))


def run(control=None):
    return harness.run_cell(CELL, seed=2**31 + 11, seconds=1e-3, trace=False,
                            require_tpu=False, control=control,
                            traffic_override=SWEEP)


def _reader():
    return harness.load_module(
        f"{harness.BENCH_DIR}/metrics/step_membership_us.py",
        "t_step_membership_us")


def test_lease5_timer_is_found_by_name_with_the_deployment_s_settings():
    from madsim_tpu.tpu import lease_workload

    c = harness.load_cell(CELL)
    assert c.chips == 1 and c.traffic["kind"] == "sweep"
    assert c.traffic["seeds_per_call"] == 131072
    assert c.config["reduced"] == []
    assert "step_membership_us" in {m["name"] for m in c.per_layer}
    assert {m["name"] for m in c.end_to_end} == {"seeds_per_s", "setup_s"}
    wl = c.factory.build(c.config, c.traffic)
    # lease_workload's nemesis and pool, with the etcd keepalive interval
    ref = lease_workload(virtual_secs=10.0).config
    assert wl.config.hash() == ref.hash()
    assert wl.config.nem_reconfig_enabled
    assert wl.spec.name == "lease5" and wl.max_steps == 100_000


def test_sound_run_is_correct():
    res = run()
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["reference_lanes"]["value"] == 64


@pytest.mark.parametrize("control,check", [
    ("zombie_unchecked", "reference_broken_lanes"),
    ("false_alarm", "violations"),
    ("deliveries_lost", "reference_progress"),
    ("half_horizon", "reference_broken_lanes"),
    ("reconfig_off", "reference_broken_lanes"),
])
def test_a_planted_break_is_not_correct(control, check):
    res = run(control)
    assert res["correct"] is False
    c = res["checks"][check]
    assert not harness.passes(c["value"], c["limit"], c["must_be"])
    if control == "zombie_unchecked":  # the device check is off
        assert res["checks"]["violations"]["value"] == 0


def _sampled(control):
    """256 lanes of the cell's workload run to the end, every lane sampled,
    with their seeds and the horizon."""
    from madsim_tpu.tpu import BatchedSim

    c = harness.load_cell(CELL)
    wl = c.factory.build(c.config, c.traffic, control)
    seeds = np.arange(256, dtype=np.uint32)
    st = BatchedSim(wl.spec, wl.config).run(jnp.asarray(seeds),
                                            max_steps=wl.max_steps)
    return (c.factory, c.factory.sample(st, np.arange(256)), seeds,
            c.factory.horizon_us(c.config, c.traffic))


@pytest.fixture(scope="module")
def sound():
    return _sampled(None)


@pytest.mark.parametrize("control", [None, "zombie_unchecked"])
def test_reference_on_256_lanes(sound, control):
    """256 sound lanes break nothing, and the Reconfig clause churned each
    one past the schedule's floor; with the zombie-lease bug and the
    device check off, the program reads clean and only the reference
    finds the stale incarnation."""
    f, picked, seeds, horizon = sound if control is None \
        else _sampled(control)
    assert not picked["violated"].any()
    bad = [b for b in f.reference(picked, seeds, horizon) if b]
    if control is None:
        assert bad == []
        assert np.mean(f.progress(picked)) >= \
            harness.load_cell(CELL).config["checks"]["progress_per_lane"]
        assert picked["reconfig_k"].min() >= horizon // f.CYCLE_US > 0
    else:
        assert bad and all(b == ["incarnation_identity"] for b in bad)


@pytest.mark.parametrize("field,change,guarantee", [
    # a remove that left the member bits whole
    ("member_p", lambda s, i: 0b11111, "membership_view"),
    # a remove or join that was not counted as a configuration change
    ("member_epoch", lambda s, i: s["member_epoch"][i] - 1,
     "membership_epoch"),
    # a join that never came
    ("reconfig_k", lambda s, i: 0, "membership_churn"),
])
def test_reference_reads_the_membership_plane(sound, field, change,
                                              guarantee):
    f, picked, seeds, horizon = sound
    lane = int(np.argmax(picked["reconf_node"] >= 0))  # one lane mid-remove
    assert picked["reconf_node"][lane] >= 0
    s = {k: v.copy() for k, v in picked.items()}
    s[field][lane] = change(s, lane)
    assert guarantee in f.reference(s, seeds, horizon)[lane]


@pytest.mark.parametrize("cell,scoped", [(CELL, True), ("raft5.sweep", False)])
def test_membership_ops_in_the_compiled_run_program(cell, scoped):
    """The lease5 `_run` program puts the Reconfig path under each phase's
    membership scope; raft5's has no such path and no such op."""
    c = harness.load_cell(cell, traffic_override={"seeds_per_call": 16})
    text = scopes.run_program_text(harness.Run(cell=c))
    phases = {p for p in _reader().membership_ops(text).values() if p}
    assert phases == ({"chaos", "network"} if scoped else set())


HLO = """\
ENTRY %main.9 (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  %fusion.1 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fc.1, metadata={op_name="jit(_run)/while/body/step/chaos/membership/and"}
  %fusion.2 = s32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fc.2, metadata={op_name="jit(_run)/while/body/step/chaos/add"}
  ROOT %fusion.3 = s32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fc.3, metadata={op_name="jit(_run)/while/body/step/network/membership/any"}
}
"""

EVENTS = {
    "devices": {"/device:TPU:0": {
        "ops": [["while.1", 100, 900],
                ["fusion.1", 100, 300],   # chaos/membership
                ["fusion.2", 400, 200],   # chaos
                ["fusion.3", 600, 100],   # network/membership
                ["fusion.1", 1500, 50]],  # the same name, another program
        "modules": [["jit__run(7)", 100, 900], ["jit__init(3)", 1500, 50]]}},
    "host": [["bench.traced.start", 0, 0], ["bench.traced.end", 2000, 0]],
}


@pytest.mark.parametrize("hlo,us", [
    (HLO, (300 + 100) / 4 / 1e3),
    (HLO.replace("/membership", ""), None),  # a program without the scope
])
def test_membership_reader_on_a_recorded_program(monkeypatch, hlo, us):
    reader = _reader()
    monkeypatch.setattr(scopes, "xplane_path", lambda run: "p.xplane.pb")
    monkeypatch.setattr(trace, "load", lambda path: EVENTS)
    monkeypatch.setattr(scopes, "run_program_text", lambda run: hlo)
    cell = types.SimpleNamespace(name="x", traffic={"trace_calls": 1})
    run = harness.Run(cell=cell, records=[{"loop_steps": 4},
                                          {"loop_steps": 99}],
                      trace=trace.reduce(EVENTS, 0, 2000))
    assert reader.read(run) == (pytest.approx(us) if us else None)
    run.trace = None
    assert reader.read(run) is None
