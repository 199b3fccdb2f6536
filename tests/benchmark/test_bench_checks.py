"""What decides `correct`, on the CPU at a size a test run can hold.

The harness runs as on the chip, minus the look for a TPU. Sound runs
come out correct; the configurations' controls (a planted break of one
of their guarantees, the device's own check off) and each fault planted
under the timed path come out not correct."""

import dataclasses

import pytest

from benchmark.lib import harness

SWEEP = {"seeds_per_call": 64, "sample_lanes_per_chunk": 64}


@pytest.fixture(autouse=True)
def out_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))


def run(cell, control=None, **over):
    return harness.run_cell(cell, seed=2**31 + 11, seconds=1e-3, trace=False,
                            require_tpu=False, control=control,
                            traffic_override=over)


def test_raft_sweep_sound_run_is_correct():
    res = run("raft5.sweep", **SWEEP)
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["reference_lanes"]["value"] == 64


def test_every_chunk_is_sampled(monkeypatch):
    """Two chunks in one call: the reference reads lanes of both, and the
    program's rows for them agree with the lanes' own states."""
    from madsim_tpu.tpu import batch

    real = batch.run_batch
    monkeypatch.setattr(batch, "run_batch",
                        lambda seeds, wl, **kw: real(seeds, wl, chunk=32, **kw))
    res = run("raft5.sweep", seeds_per_call=64, sample_lanes_per_chunk=8)
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["reference_lanes"]["value"] == 16
    assert res["checks"]["rows_disagree"]["value"] == 0


def test_raft_control_fails_the_reference():
    """The re-stamp bug with the device check off: the program reads
    clean, and only the reference sees broken log matching."""
    res = run("raft5.sweep", control="restamp_unchecked", **SWEEP)
    assert res["correct"] is False
    assert res["checks"]["violations"]["value"] == 0
    assert res["checks"]["reference_broken_lanes"]["value"] > 0


def test_kv_control_fails_the_reference(monkeypatch):
    from madsim_tpu.tpu import trace

    # the control's violating seeds would each get a 100,000-step traced
    # replay (run_batch's microscope), which the comparison never reads
    monkeypatch.setattr(trace, "trace_seed", lambda *a, **k: [])
    res = run("kv5.lin", control="stale_local_read", **SWEEP)
    assert res["correct"] is False
    assert res["checks"]["reference_broken_lanes"]["value"] > 0


def _unchanged_state(monkeypatch):
    from madsim_tpu.tpu.engine import BatchedSim

    monkeypatch.setattr(BatchedSim, "run_state",
                        lambda self, state, *a, **k: state)


def _half_the_batch(monkeypatch):
    from madsim_tpu.tpu import batch

    real = batch.run_batch
    monkeypatch.setattr(batch, "run_batch",
                        lambda seeds, wl, **kw: real(seeds[:len(seeds) // 2],
                                                     wl, **kw))


def _verdict_altered(monkeypatch):
    from madsim_tpu.tpu import batch

    real = batch.run_batch

    def altered(seeds, wl, **kw):
        r = real(seeds, wl, **kw)
        r.violated[0] = True
        return r

    monkeypatch.setattr(batch, "run_batch", altered)


def _log_altered(monkeypatch):
    """A committed entry's command changed on one node where the step
    produces it: the program's verdict stays clean."""
    from madsim_tpu.tpu.engine import BatchedSim

    real = BatchedSim.run_state

    def altered(self, state, *a, **k):
        st = real(self, state, *a, **k)
        node = st.node._replace(log_cmd=st.node.log_cmd.at[:, 0].add(1))
        return st._replace(node=node)

    monkeypatch.setattr(BatchedSim, "run_state", altered)


def _row_altered(monkeypatch):
    """A seed's retired step reported off by one by the front door."""
    from madsim_tpu.tpu import batch

    real = batch.run_batch

    def altered(seeds, wl, **kw):
        r = real(seeds, wl, **kw)
        r.retired_step[:] += 1
        return r

    monkeypatch.setattr(batch, "run_batch", altered)


def _planted(name):
    """A fault of benchmark/lib/faults.py, planted in the program the way
    `run.py --control NAME` plants it."""
    return lambda monkeypatch: name


@pytest.mark.parametrize("fault,check", [
    (_unchanged_state, "failed_seeds"),
    (_half_the_batch, "seeds_missing"),
    (_verdict_altered, "violations"),
    (_log_altered, "reference_broken_lanes"),
    (_row_altered, "rows_disagree"),
    (_planted("half_horizon"), "reference_broken_lanes"),
    (_planted("deliveries_lost"), "reference_progress"),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault, check):
    res = run("raft5.sweep", control=fault(monkeypatch), **SWEEP)
    assert res["correct"] is False
    c = res["checks"][check]
    assert not harness.passes(c["value"], c["limit"], c["must_be"])


@pytest.mark.parametrize("fault", ["half_horizon", "deliveries_lost"])
def test_kv_faults_fail_the_reference(monkeypatch, fault):
    res = run("kv5.lin", control=fault, **SWEEP)
    assert res["correct"] is False
    c = res["checks"]["reference_broken_lanes" if fault == "half_horizon"
                      else "reference_progress"]
    assert not harness.passes(c["value"], c["limit"], c["must_be"])


def test_kv_altered_read_fails_the_reference(monkeypatch):
    """A read's value changed in the recorded history: not linearizable."""
    import jax.numpy as jnp

    from madsim_tpu.tpu.engine import BatchedSim

    real = BatchedSim.run_state

    def altered(self, state, *a, **k):
        st = real(self, state, *a, **k)
        n = st.node
        return st._replace(node=n._replace(
            h_val=jnp.where(n.h_kind == 1, n.h_val + 7, n.h_val)))

    monkeypatch.setattr(BatchedSim, "run_state", altered)
    res = run("kv5.lin", **SWEEP)
    assert res["correct"] is False
    assert res["checks"]["reference_broken_lanes"]["value"] > 0


def test_triage_sound_cycle_is_correct_and_an_off_step_bundle_is_not(
        monkeypatch):
    over = {"seeds_per_cycle": 128}
    res = run("raft5.triage", **over)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0

    from madsim_tpu import triage

    real = triage.ReproBundle.load
    monkeypatch.setattr(triage.ReproBundle, "load", staticmethod(
        lambda p: dataclasses.replace(real(p),
                                      violation_step=real(p).violation_step
                                      + 1)))
    res = run("raft5.triage", **over)
    assert res["correct"] is False
    assert res["checks"]["replays_off_step"]["value"] > 0


def test_triage_false_alarm_bundle_fails_the_reference():
    """The device check fires on a sound state: the bundle replays at its
    step, and only the reference sees that no guarantee is broken."""
    res = run("raft5.triage", control="false_alarm", seeds_per_cycle=128)
    assert res["correct"] is False
    assert res["checks"]["replays_off_step"]["value"] == 0
    assert res["checks"]["bundles_reference_sound"]["value"] > 0
