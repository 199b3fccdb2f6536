"""Per-lane violation traces: the device-side repro microscope (VERDICT r3).

The reference's DX bar is exact repro from the printed seed
(runtime/mod.rs:194-199). These tests hold the batched engine to a higher
one: a violating seed re-runs ON DEVICE with full event capture, and the
captured trace alone — no host twin — is enough to see the bug mechanics.
"""

import dataclasses
import pytest

import jax
import jax.numpy as jnp
import numpy as np

from madsim_tpu import nemesis
from madsim_tpu.tpu.spec import replace_handlers
from madsim_tpu.tpu import (
    BatchedSim,
    BatchWorkload,
    SimConfig,
    default_ctl,
    make_raft_spec,
    run_batch,
    trace_seed,
)
from madsim_tpu.tpu import nemesis as tpu_nemesis
from madsim_tpu.tpu import raft as raft_mod
from madsim_tpu.tpu.engine import merge_state, split_state
from madsim_tpu.tpu.trace import extract_trace, format_trace


def partition_config(**kw):
    defaults = dict(
        horizon_us=8_000_000,
        loss_rate=0.05,
        partition_interval_lo_us=300_000,
        partition_interval_hi_us=1_500_000,
        partition_heal_lo_us=500_000,
        partition_heal_hi_us=2_000_000,
    )
    defaults.update(kw)
    return SimConfig(**defaults)


def split_brain_spec():
    """The injected bug: a leader commits on ANY single ack (no majority).
    Fatal only under partitions — a minority-side leader keeps committing
    while the majority elects a new leader and commits different entries."""
    spec = make_raft_spec(5, client_rate=0.8)

    def buggy_append_resp(s, nid, src, kind, payload, now, key):
        state, out, timer = spec.on_message(s, nid, src, kind, payload, now, key)
        is_ar = kind == raft_mod.APPEND_RESP
        success = payload[1] > 0
        match = payload[2]
        bogus_commit = jnp.where(
            is_ar & success & (state.role == raft_mod.LEADER),
            jnp.maximum(state.commit, jnp.minimum(match, state.log_len - 1)),
            state.commit,
        )
        return state._replace(commit=bogus_commit), out, timer

    return replace_handlers(spec, on_message=buggy_append_resp)


def _scan_traced(sim, seed, n_steps, ctl=None):
    """What `run_traced` must equal: a fixed-length scan of the step that
    takes all `n_steps` steps, a done lane's as well."""
    seeds = jnp.asarray([seed], jnp.uint32)
    state = sim.init(seeds) if ctl is None else sim.init(seeds, ctl)

    @jax.jit
    def scan(hot, cold, const):
        def body(carry, _):
            h, c, rec = sim._step_split(*carry, const)
            return (h, c), rec

        (h, c), recs = jax.lax.scan(body, (hot, cold), None, length=n_steps)
        return merge_state(h, c, const), recs

    return scan(*split_state(state))


_SHORT = partition_config(loss_rate=0.1, horizon_us=3_000_000)
_PLAN = nemesis.FaultPlan(name="trace-loop", clauses=(
    nemesis.Crash(interval_lo_us=400_000, interval_hi_us=1_500_000,
                  down_lo_us=300_000, down_hi_us=1_000_000),
    nemesis.Partition(interval_lo_us=300_000, interval_hi_us=1_200_000,
                      heal_lo_us=400_000, heal_hi_us=1_500_000),
))


def _loop_case(case):
    """(sim, seed, max_steps, ctl) of each way a traced lane ends."""
    if case == "violation":  # the buggy spec breaks its invariant
        return BatchedSim(split_brain_spec(), _SHORT), 0, 2_000, None
    if case == "horizon":
        return BatchedSim(make_raft_spec(5), _SHORT), 2, 2_000, None
    if case == "cap":  # still running at max_steps
        return BatchedSim(make_raft_spec(5), _SHORT), 2, 100, None
    if case == "triage":  # the ctl's horizon, a third of the config's
        sim = BatchedSim(split_brain_spec(), _SHORT, triage=True)
        return sim, 2, 2_000, default_ctl(1, 1_000_000)
    assert case == "lineage"
    cfg = tpu_nemesis.compile_plan(_PLAN, SimConfig(horizon_us=3_000_000))
    return BatchedSim(split_brain_spec(), cfg, lineage=True), 5, 2_000, None


@pytest.mark.parametrize(
    "case", ["violation", "horizon", "cap", "triage", "lineage"]
)
def test_run_traced_equals_the_fixed_length_scan(case):
    # the traced loop stops with the lane, yet every record row and every
    # final-state leaf is what stepping all max_steps steps gives
    sim, seed, max_steps, ctl = _loop_case(case)
    want = _scan_traced(sim, seed, max_steps, ctl)
    got = sim.run_traced(seed, max_steps=max_steps, ctl=ctl)
    tu = jax.tree_util
    assert tu.tree_structure(got) == tu.tree_structure(want)
    for (path, a), b in zip(tu.tree_leaves_with_path(got), tu.tree_leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), tu.keystr(path)

    state, recs = got
    assert recs.clock.shape == (max_steps, 1)
    live = int(state.steps[0]) + int(state.deadlocked[0])
    if case == "cap":
        assert not state.done[0] and live == max_steps
    else:
        assert state.done[0] and 0 < live < max_steps
    assert bool(state.violated[0]) == (case in ("violation", "lineage"))
    assert (recs.evt_eid is not None) == (case == "lineage")
    assert (state.ctl is not None) == (case == "triage")


@pytest.mark.deep
def test_trace_matches_batch_lane_bitwise():
    # the traced single-lane rerun is the SAME trajectory as the batch lane:
    # seeds, not lane positions, drive all randomness
    sim = BatchedSim(make_raft_spec(5), partition_config(horizon_us=2_000_000))
    batch = sim.run(jnp.arange(17), max_steps=20_000)  # seed 7 rides among others
    single, recs = sim.run_traced(7, max_steps=20_000)
    for name in ("clock", "steps", "events", "violated"):
        b = np.asarray(getattr(batch, name))[7]
        s = np.asarray(getattr(single, name))[0]
        assert np.array_equal(b, s), name
    for leaf_b, leaf_s in zip(
        np.asarray(batch.node.log_cmd)[7], np.asarray(single.node.log_cmd)[0]
    ):
        assert np.array_equal(leaf_b, leaf_s)


@pytest.mark.deep
def test_trace_is_deterministic():
    sim = BatchedSim(make_raft_spec(3), partition_config(horizon_us=1_000_000))
    a = trace_seed(sim, 123, max_steps=4_000)
    b = trace_seed(sim, 123, max_steps=4_000)
    assert a == b
    assert len(a) > 10


@pytest.mark.deep
def test_debug_split_brain_from_trace_alone():
    """run_batch on the buggy spec attaches a device trace for a violating
    seed; the trace alone shows the bug mechanics: a partition splits the
    cluster, then APPENDs are delivered from TWO different leaders in the
    same term window, then the committed-prefix invariant breaks."""
    wl = BatchWorkload(
        spec=split_brain_spec(),
        config=partition_config(loss_rate=0.1),
        max_steps=60_000,
    )
    result = run_batch(range(256), wl, repro_on_host=False, max_traces=1)
    assert result.violations > 0
    assert result.summary["violation_lanes"] == list(
        np.nonzero(result.violated)[0][:32]
    )
    seed, events = next(iter(result.traces.items()))
    assert result.violated[seed]
    text = format_trace(events)
    assert "partition split" in text

    # the trace ends at the violation
    kinds = [e.kind for e in events]
    assert "violation" in kinds
    vio_i = kinds.index("violation")

    # find the last split before the violation, with no heal in between:
    # the partition that exposed the bug
    last_split = max(
        i for i, e in enumerate(events[:vio_i]) if e.kind == "split"
    )
    window = events[last_split:vio_i]
    assert not any(e.kind == "heal" for e in window)

    # split-brain visible in the window, via either catch mechanism, each
    # with its own precise signature:
    # (a) committed-prefix divergence — APPEND traffic from >= 2 distinct
    #     sources (the two concurrent leaders actively diverging), or
    # (b) Leader Completeness firing the moment the other side's candidate
    #     WINS: the last delivery before the violation is the winning
    #     VOTE_RESP, received by a node that is not the appender whose
    #     bogus commits it is missing
    append_srcs = {
        e.src for e in window if e.kind == "deliver" and e.msg_name == "APPEND"
    }
    deliveries = [e for e in window if e.kind == "deliver"]
    two_leaders_appending = len(append_srcs) >= 2
    incomplete_leader_at_election = (
        bool(append_srcs)
        and bool(deliveries)
        and deliveries[-1].msg_name == "VOTE_RESP"
        and deliveries[-1].node not in append_srcs
    )
    assert two_leaders_appending or incomplete_leader_at_election, format_trace(
        window
    )


@pytest.mark.deep
def test_trace_records_crash_restart():
    sim = BatchedSim(
        make_raft_spec(5),
        SimConfig(
            horizon_us=3_000_000,
            crash_interval_lo_us=300_000,
            crash_interval_hi_us=1_000_000,
            restart_delay_lo_us=200_000,
            restart_delay_hi_us=600_000,
        ),
    )
    events = trace_seed(sim, 5, max_steps=20_000, kind_names=("RV", "VR", "AE", "AR", "SN"))
    kinds = [e.kind for e in events]
    assert "crash" in kinds and "restart" in kinds
    # a crash of node k is followed by a restart of the same node
    crash_e = next(e for e in events if e.kind == "crash")
    restart_e = next(e for e in events if e.kind == "restart")
    assert crash_e.node == restart_e.node
    assert restart_e.t_us > crash_e.t_us
