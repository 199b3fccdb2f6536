"""Donation + pipelining determinism, and the dispatch budget (r6).

The r6 perf work changed HOW sweeps execute — carry buffers are donated
across sweep segments, run_batch double-buffers its chunk loop, triage
overlaps ddmin generation chunks — while the CONTRACT is that none of it
may change a single bit of any result. These tests pin that contract, and
the dispatch budget pins the sweep's launch count so eager-init-style
regressions (the r5 ~1.4 s/sweep dispatch-storm bug) fail loudly.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu.tpu import BatchedSim, SimConfig, make_raft_spec, raft_workload
from madsim_tpu.tpu.batch import run_batch


def _leaves_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _tiny_workload(virtual_secs: float = 0.6):
    wl = raft_workload(virtual_secs=virtual_secs)
    return dataclasses.replace(wl, max_steps=2_500, host_repro=None)


# ------------------------------------------------------------- donation


def test_donated_sweep_bit_identical_to_undonated():
    """The donated segment function must produce the exact state an
    undonated copy of the SAME body produces — donation is an aliasing
    hint, never a semantic one."""
    spec = make_raft_spec(5)
    cfg = SimConfig(
        horizon_us=400_000,
        loss_rate=0.1,
        crash_interval_lo_us=100_000,
        crash_interval_hi_us=300_000,
        partition_interval_lo_us=100_000,
        partition_interval_hi_us=300_000,
    )
    sim = BatchedSim(spec, cfg)
    seeds = jnp.arange(48)
    # an undonated jit of the same underlying body
    undonated = jax.jit(
        BatchedSim._run.__wrapped__, static_argnums=(0, 2)
    )
    ref = undonated(sim, sim.init(seeds), 600)
    out = sim._run(sim.init(seeds), 600)  # the donated production path
    assert _leaves_equal(ref, out)


def test_donated_run_end_to_end_deterministic():
    """Two full run() sweeps of the same seeds through the donated
    chunked path stay bit-identical (the donated buffers are never read
    after reuse)."""
    sim = BatchedSim(make_raft_spec(5), SimConfig(horizon_us=500_000))
    a = sim.run(jnp.arange(32), max_steps=1_500, dispatch_steps=400)
    b = sim.run(jnp.arange(32), max_steps=1_500, dispatch_steps=400)
    assert _leaves_equal(a, b)


# ----------------------------------------------------------- pipelining


def _strip_timing(summary):
    return {k: v for k, v in summary.items() if k != "wall_ms"}


def test_pipelined_run_batch_bit_identical_to_serial():
    """Chunked sweeps, pipelined vs serial: identical violation lanes,
    identical final state, identical summaries (incl. chaos_fires) —
    pipelining only moves the host's READ order."""
    wl = _tiny_workload()
    kw = dict(chunk=16, mesh=None, max_traces=0, repro_on_host=False)
    piped = run_batch(range(48), wl, pipeline=True, **kw)
    serial = run_batch(range(48), wl, pipeline=False, **kw)
    assert np.array_equal(piped.violated, serial.violated)
    assert np.array_equal(piped.deadlocked, serial.deadlocked)
    assert piped.chaos_fires == serial.chaos_fires
    assert _strip_timing(piped.summary) == _strip_timing(serial.summary)
    assert _leaves_equal(piped.state, serial.state)


@pytest.mark.slow
def test_pipelined_run_batch_big_sweep_bit_identical():
    """The 1024-seed acceptance variant of the pipelining contract, with
    chaos on so violation lanes and chaos_fires are exercised for real."""
    wl = raft_workload(virtual_secs=3.0)
    wl = dataclasses.replace(wl, max_steps=6_000, host_repro=None)
    kw = dict(chunk=256, mesh=None, max_traces=0, repro_on_host=False)
    piped = run_batch(range(1024), wl, pipeline=True, **kw)
    serial = run_batch(range(1024), wl, pipeline=False, **kw)
    assert np.array_equal(piped.violated, serial.violated)
    assert piped.chaos_fires == serial.chaos_fires
    assert _strip_timing(piped.summary) == _strip_timing(serial.summary)
    assert _leaves_equal(piped.state, serial.state)


@pytest.mark.slow
def test_donated_sweep_big_bit_identity():
    """Big-sweep donation identity: the chunked donated path at several
    segments equals a fresh undonated execution, leaf for leaf."""
    spec = make_raft_spec(5)
    cfg = SimConfig(
        horizon_us=3_000_000,
        loss_rate=0.1,
        crash_interval_lo_us=400_000,
        crash_interval_hi_us=1_500_000,
        partition_interval_lo_us=300_000,
        partition_interval_hi_us=1_200_000,
    )
    sim = BatchedSim(spec, cfg)
    undonated = jax.jit(
        BatchedSim._run.__wrapped__, static_argnums=(0, 2)
    )
    ref = undonated(sim, sim.init(jnp.arange(256)), 4_000)
    out = sim.run(jnp.arange(256), max_steps=4_000, dispatch_steps=1_000)
    assert _leaves_equal(ref, out)


# -------------------------------------------------------- dispatch budget


def test_dispatch_budget_single_chunk():
    """One chunk, one segment: exactly TWO device program launches (jitted
    init + one while_loop segment). An eager init is dozens; a
    step-granular loop would be thousands — both blow this loudly."""
    wl = _tiny_workload()
    res = run_batch(
        range(64), wl, mesh=None, max_traces=0, repro_on_host=False
    )
    assert res.dispatches == 2, res.dispatches
    assert res.summary["dispatches"] == 2
    assert res.wall_ms > 0


def test_dispatch_budget_chunked():
    """k chunks of one segment each: exactly 2k launches, and the budget
    scales with chunks, not with steps or lanes."""
    wl = _tiny_workload()
    res = run_batch(
        range(64), wl, chunk=16, mesh=None, max_traces=0,
        repro_on_host=False,
    )
    assert res.dispatches == 8, res.dispatches  # 4 chunks x (init + run)


def test_init_is_one_jitted_program():
    """The r5 regression shape: sweep init must be ONE compiled program,
    not eager per-op dispatches. jax.jit exposes .lower on the wrapper —
    an un-jitted init loses it (and the budget above catches the launch
    storm)."""
    sim = BatchedSim(make_raft_spec(5), SimConfig(horizon_us=200_000))
    assert hasattr(sim.init, "lower")
    assert hasattr(sim._run, "lower")
    before = sim.dispatch_count
    sim.run(jnp.arange(8), max_steps=200)
    assert sim.dispatch_count - before == 2


# ------------------------------------------------ coverage instrumentation


def test_coverage_bitmap_identical_across_repeats_and_pipeline():
    """The explorer's novelty signal must be bit-deterministic: the same
    seeds produce the same per-lane bitmaps, occurrence fires and scalar
    features on every run, chunked or not, pipelined or serial (the
    decode order never touches device results)."""
    wl = _tiny_workload()
    kw = dict(mesh=None, max_traces=0, repro_on_host=False, coverage=True)
    a = run_batch(range(48), wl, chunk=16, pipeline=True, **kw)
    b = run_batch(range(48), wl, chunk=16, pipeline=False, **kw)
    c = run_batch(range(48), wl, chunk=48, pipeline=True, **kw)
    for other in (b, c):
        assert np.array_equal(a.coverage.bitmap, other.coverage.bitmap)
        assert np.array_equal(a.coverage.hiwater, other.coverage.hiwater)
        assert np.array_equal(
            a.coverage.transitions, other.coverage.transitions
        )
        assert a.summary["coverage_bits"] == other.summary["coverage_bits"]
    assert a.coverage.bitmap.shape == (48, 256)
    assert a.summary["coverage_bits"] == a.coverage.union_bits() > 0
    # coverage off: no bitmap cost, no coverage field
    plain = run_batch(
        range(48), wl, chunk=48, mesh=None, max_traces=0,
        repro_on_host=False,
    )
    assert plain.coverage is None
    assert "coverage_bits" not in plain.summary


def test_coverage_on_donated_path_bit_identical():
    """Donation must not perturb the coverage accumulators: the donated
    segment function's Coverage leaves equal an undonated execution of
    the same body."""
    spec = make_raft_spec(5)
    cfg = SimConfig(horizon_us=400_000, loss_rate=0.1)
    sim = BatchedSim(spec, cfg, coverage=True)
    seeds = jnp.arange(32)
    undonated = jax.jit(
        BatchedSim._run.__wrapped__, static_argnums=(0, 2)
    )
    ref = undonated(sim, sim.init(seeds), 600)
    out = sim._run(sim.init(seeds), 600)
    assert _leaves_equal(ref.cov, out.cov)
    assert _leaves_equal(ref, out)


# ------------------------------------------------- twopc fused-path parity


def _twopc_parity_cfg():
    return SimConfig(
        horizon_us=2_000_000,
        msg_capacity=128,
        loss_rate=0.1,
        crash_interval_lo_us=400_000,
        crash_interval_hi_us=2_000_000,
        restart_delay_lo_us=200_000,
        restart_delay_hi_us=1_000_000,
        partition_interval_lo_us=400_000,
        partition_interval_hi_us=1_500_000,
        partition_heal_lo_us=300_000,
        partition_heal_hi_us=1_200_000,
    )


# sha256 over the final-state leaves (tree order) of the R5 per-kind
# twopc handlers (lax.switch h_prepare/h_vote/h_outcome/h_dreq +
# fuse_two_handlers) on _twopc_parity_cfg, seeds 0..31, 8k steps, CPU —
# captured from the pre-r6 module at the commit that replaced it. The
# r6 hand-fused on_event claims bit-identity with those handlers; this
# digest is the in-tree witness (the wrapper-vs-fused comparison below
# alone would be circular: both sides share the fused body).
#
# LAYOUT-VERSION r8 re-bless: this digest hashes the RAW at-rest leaves,
# so the r8 carry compaction (twopc narrow_fields i16/u8 storage +
# bit-packed valid planes) changed it with NO trajectory change. The
# trajectory-level equivalence old-layout == new-layout is pinned
# separately by tests/test_state_layout.py's canonical golden digests
# (twopc constant produced identically by the r7 and r8 engines), so the
# witness chain r5-handlers == r6-fused == r8-compacted is unbroken.
# Pre-r8 value: 3257fd77792c2139b2264c2f2c75776260c7cebe38add0aa783f674aa1fa46c6
_R5_TWOPC_DIGEST = (
    "294c54ac291e30ceddf114b09a5654893048edfe27bafe90189d0efb019713ac"
)


def _state_digest(state) -> str:
    import hashlib

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(state):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.skipif(
    jax.default_backend() != "cpu",
    reason="golden digest captured on the CPU backend (per-backend "
    "determinism contract: trajectories are pinned per backend)",
)
def test_twopc_hand_fused_matches_r5_golden_trajectory():
    """The hand-fused twopc must reproduce the EXACT trajectory of the
    deleted r5 per-kind handlers — pinned by a digest captured from the
    old module, so a transcription error in the masked merge cannot
    hide behind a self-consistent wrong body."""
    from madsim_tpu.tpu.twopc import make_twopc_spec

    state = BatchedSim(make_twopc_spec(5), _twopc_parity_cfg()).run(
        jnp.arange(32), max_steps=8_000
    )
    assert _state_digest(state) == _R5_TWOPC_DIGEST


def test_twopc_hand_fused_matches_generic_fusion():
    """The hand-fused on_event must also equal the generic
    fuse_two_handlers wrapping of its own derived two-handler view (this
    pins the wrapper plumbing; the golden-digest test above pins the
    body itself against r5)."""
    from madsim_tpu.tpu.spec import fuse_two_handlers
    from madsim_tpu.tpu.twopc import make_twopc_spec

    cfg = _twopc_parity_cfg()
    hand = make_twopc_spec(5)
    generic = fuse_two_handlers(
        dataclasses.replace(hand, on_event=None)
    )
    a = BatchedSim(hand, cfg).run(jnp.arange(32), max_steps=8_000)
    b = BatchedSim(generic, cfg).run(jnp.arange(32), max_steps=8_000)
    assert _leaves_equal(a, b)
