"""run_batch: the host↔TPU bridge — whole seed sweeps as one device batch.

This replaces the reference's thread-per-seed fan-out
(madsim/src/sim/runtime/builder.rs:118-136) for device-expressible workloads:
instead of `MADSIM_TEST_NUM` OS threads each running a full host simulation,
the entire seed range becomes lanes of one `BatchedSim` batch, fuzzed in a
handful of jitted steps on TPU. Violating lanes come back as *seeds*, and each
violating seed is re-run on the single-lane host runtime (`host_repro`) for
full-fidelity debugging — print statements, Python breakpoints, per-node logs.

The determinism contract is per-backend (SURVEY.md §7 step 1): a seed is
bit-reproducible *within* a backend. The TPU engine is the wide net; the host
runtime is the microscope. A workload provides both faces:

    workload = BatchWorkload(
        spec=make_raft_spec(n_nodes=5),
        config=SimConfig(loss_rate=0.1, ...),
        host_repro=lambda seed: fuzz_one_seed(seed, ...),  # optional
    )
    result = run_batch(range(10_000), workload)
    result.raise_on_violation()    # TestFailure with repro seeds

or, as a test (the `#[madsim::test]` analog for batched workloads):

    @batch_test(workload)
    def test_raft_fuzz(result):
        assert result.violations == 0
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from .. import telemetry
from .engine import (BatchedSim, DEFAULT_DISPATCH_STEPS, SUM64_MAX_LANES,
                     SimState, summarize)
from .spec import ProtocolSpec, SimConfig

# Lanes PER DEVICE of one chunked dispatch. Compiled for a v5e, the headline
# raft `_run`'s temporaries are 0.14x its argument bytes at 16,384 lanes,
# 1.07x at 32,768 and 1.97x at 65,536, where the step spills through extra
# async copies: a lane-iteration costs 37.95, 43.77 and 60.73 ns on a v5e.
# 16,384 lanes would double the chunk boundaries, whose unoverlapped decode
# costs a short-step workload (lease5) about 4.5% end to end (PERF.md).
DEFAULT_CHUNK = 32_768


def default_chunk(n_devices: int) -> int:
    """run_batch's chunk when the caller names none: DEFAULT_CHUNK lanes a
    device, capped at the lanes `engine._sum64` sums exactly in one chunk's
    summary (65,536: one chip runs 32,768 lanes a dispatch, four chips
    16,384 each)."""
    return min(DEFAULT_CHUNK * n_devices, SUM64_MAX_LANES)


@dataclasses.dataclass(frozen=True)
class BatchWorkload:
    """A protocol's two faces: the TPU spec + the host-runtime reproducer.

    `host_repro(seed)` runs ONE seed on the host runtime (madsim_tpu.core),
    raising or returning a dict with a truthy "violations"/"violation" entry
    when the bug reproduces. It does not need to match the TPU trajectory
    bit-for-bit — it is the debugging microscope, not a replay.
    """

    spec: ProtocolSpec
    config: Optional[SimConfig] = None
    host_repro: Optional[Callable[[int], Any]] = None
    max_steps: int = 100_000
    # optional deep oracle over recorded per-lane histories, run host-side
    # by run_batch on every violating lane PLUS a sampled clean subset
    # (cheap device invariants are the wide net; this is the exact check —
    # e.g. kv_workload wires per-key Wing-Gong linearizability here).
    # Signature: lane_check(final_chunk_state, lane_indices) -> dict with
    # integer counters (merged across chunks) incl. a "violations" count.
    lane_check: Optional[Callable[[Any, Sequence[int]], dict]] = None
    lane_check_sample: int = 8


@dataclasses.dataclass
class LaneCoverage:
    """Per-lane coverage decoded from a sweep (run_batch(coverage=True)).

    The raw material of the explorer's novelty ranking (madsim_tpu/explore):
    each lane's event-class bitmap, its clause x occurrence fire bitmasks
    (None when no nemesis schedule clause is enabled), and the scalar
    features. Chunked sweeps concatenate in seed order.
    """

    bitmap: np.ndarray  # u32 [L, engine.COV_WORDS]
    occ_fired: Optional[np.ndarray]  # u32 [L, len(OCC_CLAUSES)] | None
    hiwater: np.ndarray  # i32 [L]
    transitions: np.ndarray  # i32 [L]

    def union_bits(self) -> int:
        """Distinct event-class bits exercised across all lanes."""
        from ..explore import popcount_rows

        union = np.bitwise_or.reduce(self.bitmap, axis=0)
        return int(popcount_rows(union))


class BatchDeterminismError(AssertionError):
    """Two runs of the same seed batch diverged (the device analog of the
    reference's MADSIM_TEST_CHECK_DETERMINISM RNG-trace comparison,
    rand.rs:63-111 / runtime/mod.rs:167-191)."""


def _assert_runs_bitwise_equal(a: SimState, b: SimState, context: str) -> None:
    leaves_a, treedef = jax.tree_util.tree_flatten(a)
    leaves_b = jax.tree_util.tree_leaves(b)
    for i, (x, y) in enumerate(zip(leaves_a, leaves_b)):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            raise BatchDeterminismError(
                f"determinism check failed ({context}): state leaf {i} of "
                f"{treedef.num_leaves} differs between two runs of the same "
                "seeds — the spec or backend is nondeterministic"
            )


class BatchViolation(AssertionError):
    """Violations found in a batch; carries repro seeds (builder.rs DX
    analog), the exact single-seed repro command, and — when the sweep ran
    with shrink_on_violation — the shrunk repro bundle's path and replay
    one-liner (madsim_tpu/triage.py)."""

    def __init__(
        self, seeds: List[int], detail: str,
        bundle_path: Optional[str] = None,
        bundle: Any = None,
    ) -> None:
        from ..testing import single_seed_repro_command

        shown = ", ".join(str(s) for s in seeds[:16])
        more = "" if len(seeds) <= 16 else f" (+{len(seeds) - 16} more)"
        self.repro_command = single_seed_repro_command(seeds[0])
        self.bundle_path = bundle_path
        msg = (
            f"{len(seeds)} violating seed(s): {shown}{more}\n    {detail}\n"
            f"    reproduce one with: {self.repro_command}"
        )
        if bundle_path:
            msg += f"\n    shrunk repro bundle: {bundle_path}"
            if bundle is not None and not getattr(bundle, "spec_ref", None):
                # a bundle without a spec factory reference can't rebuild
                # the ProtocolSpec in a fresh process — advertise only the
                # commands that actually work, and say what's missing
                msg += (
                    f"\n    replay the shrunk fault schedule with: "
                    f"python -m madsim_tpu.repro {bundle_path} --backend host"
                    f"\n    (device replay needs --spec-ref "
                    f"'your.module:spec_factory' — or pass spec_ref= in "
                    f"shrink_kwargs to bake it into the bundle)"
                )
            else:
                msg += (
                    f"\n    replay it with: "
                    f"python -m madsim_tpu.repro {bundle_path}"
                )
        super().__init__(msg)
        self.seeds = seeds


@dataclasses.dataclass
class BatchResult:
    """Outcome of one batched sweep."""

    seeds: np.ndarray  # [L] the seeds that ran
    violated: np.ndarray  # [L] bool
    deadlocked: np.ndarray  # [L] bool
    summary: Dict[str, Any]
    state: SimState  # final engine state (chunked runs: last chunk only)
    host_repros: Dict[int, Any] = dataclasses.field(default_factory=dict)
    # per-seed device event traces for violating seeds (trace.TraceEvent
    # lists): the full trajectory that violated — deliveries, timers,
    # crashes, partitions — debuggable with no host twin
    traces: Dict[int, list] = dataclasses.field(default_factory=dict)
    # the workload that ran (so .shrink() can rebuild the triage sim), and
    # the shrunk repro bundle when run_batch(shrink_on_violation=True)
    workload: Optional["BatchWorkload"] = None
    bundle: Any = None  # triage.ReproBundle | None
    bundle_path: Optional[str] = None
    # per-lane coverage (run_batch(coverage=True) only): the explorer's
    # novelty signal, concatenated across chunks in seed order
    coverage: Optional[LaneCoverage] = None
    # sweep-overhead visibility without running benches: how many device
    # program launches the sweep itself cost (init + run segments +
    # sharding puts, via BatchedSim.dispatch_count — excludes post-sweep
    # traces/shrinks), and the sweep loop's host wall time in ms (dispatch
    # through readback of the last chunk; not device time). The
    # dispatch-budget regression test pins `dispatches` so eager-init-style
    # regressions (r5's ~1.4 s/sweep of per-op dispatch latency) can't
    # silently return.
    dispatches: int = 0
    wall_ms: float = 0.0
    # -- continuous batching (r9, docs/continuous_batching.md) --
    # lane occupancy: busy-lane-steps / total-lane-steps over the sweep.
    # Exact on the refill path (engine counters); on the chunked path an
    # estimate from per-lane step counts (each chunk's denominator is its
    # longest lane's step count), reported so refill-vs-chunked reads off
    # one field. per-admission rows ride along in seed order: the step at
    # which each admission retired (refill: global sweep step; chunked: the
    # lane's own final step count — lanes start together, so the two agree
    # up to chunk phase) and its first violating step (-1 = none).
    occupancy: Optional[float] = None
    retired_step: Optional[np.ndarray] = None  # i32 [L]
    violation_step: Optional[np.ndarray] = None  # i32 [L]

    @property
    def violations(self) -> int:
        return int(self.violated.sum())

    @property
    def chaos_fires(self) -> Dict[str, int]:
        """Per-fault-kind fire counts over the whole batch (the device
        half of the chaos-coverage report; see madsim_tpu/nemesis.py)."""
        return {
            k[len("fires_"):]: v
            for k, v in self.summary.items()
            if k.startswith("fires_")
        }

    def chaos_report(self) -> str:
        """The rendered chaos-coverage line ('' when no chaos enabled)."""
        return self.summary.get("chaos_coverage", "")

    @property
    def violating_seeds(self) -> List[int]:
        return [int(s) for s in self.seeds[self.violated]]

    def shrink(self, seed: Optional[int] = None, **kwargs):
        """Shrink one violating seed (default: the first) into a minimal,
        portable repro bundle — see madsim_tpu.triage.shrink_seed for the
        keyword surface (out_dir, spec_ref, lane_width, ...). Returns the
        triage.ShrinkResult and remembers the bundle on this result."""
        from .. import triage

        if self.workload is None:
            raise ValueError(
                "this BatchResult carries no workload — run it through "
                "run_batch (or set result.workload) before shrinking"
            )
        if seed is None:
            if not self.violations:
                raise ValueError("no violating seeds to shrink")
            seed = self.violating_seeds[0]
        kwargs.setdefault("out_dir", triage.default_bundle_dir())
        sr = triage.shrink_seed(self.workload, seed, **kwargs)
        self.bundle = sr.bundle
        self.bundle_path = sr.bundle_path
        return sr

    def raise_on_violation(self) -> None:
        if self.violations:
            raise BatchViolation(
                self.violating_seeds,
                f"summary: {self.summary}",
                bundle_path=self.bundle_path,
                bundle=self.bundle,
            )


def resolve_mesh(mesh) -> Optional[Any]:
    """Resolve run_batch's mesh argument.

    "auto" (the default) builds a 1-D lane mesh over EVERY visible device —
    the reference's execution model uses all available parallel hardware
    for a seed sweep (one OS thread per seed, `jobs` concurrent,
    runtime/builder.rs:118-136); a user with a v5e-8 gets all 8 chips
    without hand-sharding. None (or a single device) runs unsharded; a
    jax.sharding.Mesh is used as-is (first axis = lanes).
    """
    if mesh is None:
        return None
    if mesh == "auto":
        import jax

        devices = jax.devices()
        if len(devices) <= 1:
            return None
        return jax.sharding.Mesh(np.array(devices), ("seeds",))
    return mesh


def pipelined(items, dispatch, decode, serial: bool = False):
    """Double-buffered dispatch/decode loop — the chunk pipeline shared by
    run_batch, triage's ddmin generations, and benches/ttfb.py.

    `dispatch(item)` launches one chunk's device work and returns an entry
    without waiting on results; `decode(entry)` reads the chunk's small
    outputs (this is where the host blocks). Item k+1 is dispatched BEFORE
    entry k is decoded, so host decoding overlaps device time. Decode
    order stays item order, so any aggregation inside `decode` is
    byte-for-byte what the serial loop produces.

    The first non-None value returned by `decode` short-circuits the loop
    (the in-flight chunk, if any, is dropped undecoded — the price of the
    overlap) and becomes this function's return value. `serial=True`
    decodes each entry immediately after its dispatch (same results, no
    overlap) — the reference loop the pipelining tests compare against.
    """
    pending = None
    for item in items:
        entry = dispatch(item)
        if serial:
            hit = decode(entry)
            if hit is not None:
                return hit
        else:
            if pending is not None:
                hit = decode(pending)
                if hit is not None:
                    return hit
            pending = entry
    if pending is not None:
        return decode(pending)
    return None


def run_batch(
    seeds: Sequence[int],
    workload: BatchWorkload,
    repro_on_host: bool = True,
    max_host_repros: int = 4,
    chunk: Optional[int] = None,
    max_traces: int = 2,
    mesh: Any = "auto",
    check_determinism: bool = False,
    shrink_on_violation: bool = False,
    shrink_kwargs: Optional[Dict[str, Any]] = None,
    pipeline: Optional[bool] = None,
    coverage: bool = False,
    refill: Optional[int] = None,
    dispatch_steps: Optional[int] = None,
    sim: Optional[BatchedSim] = None,
    tuning: Any = None,
) -> BatchResult:
    """Fuzz every seed as one TPU batch; re-run violating seeds on the host.

    `check_determinism` runs every chunk TWICE and bitwise-compares the
    full final states (the reference's MADSIM_TEST_CHECK_DETERMINISM mode;
    `@batch_test` turns it on from that same env var). The engine is
    deterministic by construction, so this is a tripwire for impure specs
    and misbehaving backends. Both runs dispatch the same program on the
    same inputs, so anything between host and device that memoizes
    identical dispatches would mask backend-level nondeterminism;
    spec-level impurity still bakes in at trace time and is caught.

    The TPU pass is the seed sweep (runtime/builder.rs:110-148 made wide)
    over ALL visible devices by default (see `resolve_mesh`); the host pass
    is the repro DX (builder.rs prints the failing seed — here the failing
    seed is actually *re-executed* on the debuggable runtime). Per-seed
    results are bit-identical whatever the mesh: no engine draw folds the
    lane index, so a trajectory never depends on which device (or batch
    position) its lane landed on.

    `shrink_on_violation` closes the triage loop: the first violating seed
    is automatically ddmin-shrunk into a minimal, portable repro bundle
    (madsim_tpu/triage.py; a handful of extra batched dispatches), written
    under triage.default_bundle_dir() unless shrink_kwargs["out_dir"] says
    otherwise, and reported in BatchViolation with its replay one-liner.

    `pipeline` (default on) double-buffers the chunk loop: chunk k+1's
    device program is dispatched BEFORE the host decodes chunk k's
    violation/metrics scalars, so host-side decoding (summarize, the
    lane_check oracle) overlaps the next chunk's device time instead of
    serializing with it — JAX async dispatch does the rest, and the host
    only ever blocks on the small reduction outputs it is reading. Results
    are bit-identical to the serial loop (the device programs and their
    inputs are unchanged; only the host's read order moves), which the
    pipelining-determinism tests pin.

    `chunk` is the lanes of one dispatch on the chunked path (on the
    refill path, the seeds of one queue segment). None takes
    `default_chunk`: DEFAULT_CHUNK = 32,768 lanes a device, at most
    65,536 in all, the widest lane axis `engine._sum64` sums exactly —
    one chip runs 32,768 lanes a dispatch, four chips 16,384 each. At
    65,536 lanes on one v5e the headline step's compiled temporaries are
    twice its arguments and a lane-iteration costs 1.39x more (the table
    at DEFAULT_CHUNK). An explicit `chunk`, then a tuned one, wins. The summary reports the
    plan that ran: `chunk` (lanes a dispatch) and `chunks` (dispatches).

    `coverage` turns on the per-lane coverage instrumentation (the
    explorer's novelty signal, madsim_tpu/explore.py): the result carries a
    `LaneCoverage` and the summary a `coverage_bits` union count. Off by
    default — the bitmap costs a few percent of step time.

    `tuning` consults the measured tuned-config cache (madsim_tpu/tune.py,
    docs/tuning.md): pass ``"auto"`` to look up this device's entry for
    (workload, config, lane count) and apply its TIER-A dispatch knobs —
    chunk, segment length, pipeline, refill lane width, mesh device
    count. Tier A is result-invariant by the engine's bit-identity
    contract, so a tuned sweep's per-seed rows equal the default sweep's
    bit-for-bit (tests/test_tune.py); a cache miss runs the hand-pinned
    defaults. Explicit arguments win over tuned values — including an
    explicit ``refill=0``, which pins the chunked path (and its per-lane
    summary schema) whatever the cache holds; Tier-B (config)
    knobs are never applied here — they fold into the SimConfig at
    config-creation time only. `dispatch_steps` overrides the engine
    segment length (None = the engine default); `sim` passes a pre-built
    BatchedSim so repeated sweeps (the tuner's trials, bench A/B loops)
    amortize the compile instead of re-jitting per call.

    `refill=<lanes>` runs the sweep CONTINUOUSLY BATCHED over that many
    device lanes PER DEVICE (docs/continuous_batching.md +
    docs/multichip.md): a lane that finishes — violates or reaches its
    horizon — retires and admits the next queued seed inside the jitted
    loop, so heterogeneous-length seeds never leave the chip idling on
    finished lanes. Each `chunk` of seeds is one device-resident queue
    segment; the host tops the queue up between segments through the
    same `pipelined` loop. The mesh is HONORED (r10): with more than
    one device (mesh="auto" or an explicit mesh) each chunk's seed list
    is partitioned into one contiguous sub-queue per device and the
    segment runs as ONE shard_map'd program — each device owns its
    sub-queue, its `refill` lanes and its result buffers, with zero
    cross-device collectives inside the step (gathers at segment end
    only). Per-seed results are BIT-IDENTICAL to the chunked path AND
    across device counts (tested): an admission's trajectory is the
    pure per-seed function either way, and decode reads the
    per-admission result rows in admission (= seed) order. Restriction:
    the refill path keeps no final node state per admission, so
    workloads with a `lane_check` deep oracle (and spec lane_metrics
    diagnostics) must run chunked.
    """
    seeds_arr = np.asarray(list(seeds), dtype=np.uint32)
    if seeds_arr.ndim != 1 or seeds_arr.size == 0:
        raise ValueError("seeds must be a non-empty 1-D sequence")
    if tuning is not None:
        # Tier-A dispatch knobs from the tuned-config cache. Application
        # rule: a tuned value lands only where the caller OMITTED the
        # parameter (None sentinels) — an explicitly passed argument
        # always wins, even one equal to the default — and every knob
        # applied here is result-invariant (bit-identity matrix in
        # tests/test_tune.py), so this is a pure throughput decision,
        # never a behavioral one.
        from .. import tune as _tune

        tn = _tune.resolve_tuning(
            tuning, workload.spec.name, workload.config or SimConfig(),
            seeds_arr.size,
        )
        if "chunk" in tn and chunk is None:
            chunk = int(tn["chunk"])
        if "pipeline" in tn and pipeline is None:
            pipeline = bool(tn["pipeline"])
        if "dispatch_steps" in tn and dispatch_steps is None:
            dispatch_steps = int(tn["dispatch_steps"])
        if (
            "refill_lanes" in tn and refill is None
            and workload.lane_check is None
        ):
            refill = int(tn["refill_lanes"])
        if "devices" in tn and mesh == "auto":
            # cached=True: an entry recorded on a bigger host (more
            # visible devices) degrades to the production default mesh
            # instead of killing the sweep — a cache can only ever be a
            # throughput upgrade, never a crash
            mesh = _tune._mesh_for(tn["devices"], cached=True)
    mesh = resolve_mesh(mesh)
    if chunk is None:
        chunk = default_chunk(mesh.devices.size if mesh is not None else 1)
    if pipeline is None:
        pipeline = True
    if refill is None:
        refill = 0
    if refill and workload.lane_check is not None:
        raise ValueError(
            "run_batch(refill=...) keeps no per-admission node state, so "
            "lane_check deep oracles cannot run — use the chunked path "
            "(refill=0) or strip the workload's lane_check"
        )
    if sim is None:
        sim = BatchedSim(workload.spec, workload.config, coverage=coverage)
    elif bool(sim.coverage) != bool(coverage):
        raise ValueError(
            f"run_batch(coverage={coverage}) with a pre-built sim whose "
            f"coverage={sim.coverage} — build the sim to match"
        )
    elif sim.spec is not workload.spec or sim.config.hash() != (
        workload.config or SimConfig()
    ).hash():
        # a sim built for another (spec, config) would fuzz a DIFFERENT
        # program while summaries, violation rows and host repro are all
        # attributed to `workload` — the host replay would silently
        # disagree with the device verdicts. Loud, like every other
        # identity mismatch in this tree.
        raise ValueError(
            "run_batch(sim=...) was built for a different (spec, config) "
            f"than the workload: sim runs {sim.spec.name!r} "
            f"cfg={sim.config.hash()[:12]} but the workload is "
            f"{workload.spec.name!r} "
            f"cfg={(workload.config or SimConfig()).hash()[:12]} — "
            "pre-built sims amortize compiles for the SAME program only"
        )
    if dispatch_steps is None:
        dispatch_steps = DEFAULT_DISPATCH_STEPS
    common = dict(
        chunk=chunk, mesh=mesh, pipeline=pipeline,
        coverage=coverage, check_determinism=check_determinism,
        repro_on_host=repro_on_host, max_host_repros=max_host_repros,
        max_traces=max_traces, shrink_on_violation=shrink_on_violation,
        shrink_kwargs=shrink_kwargs, dispatch_steps=dispatch_steps,
    )
    if refill:
        with telemetry.span("run_batch", site="refill"):
            return _run_batch_refill(
                seeds_arr, workload, sim, int(refill), **common
            )
    with telemetry.span("run_batch", site="chunked"):
        return _run_batch_chunked(seeds_arr, workload, sim, **common)


def _run_batch_chunked(
    seeds_arr: np.ndarray,
    workload: BatchWorkload,
    sim: BatchedSim,
    chunk: int,
    mesh: Optional[Any],
    pipeline: bool,
    coverage: bool,
    check_determinism: bool,
    repro_on_host: bool,
    max_host_repros: int,
    max_traces: int,
    shrink_on_violation: bool,
    shrink_kwargs: Optional[Dict[str, Any]],
    dispatch_steps: int,
) -> BatchResult:
    """run_batch's chunked sweep: each `chunk` of seeds is one `sim.run`
    over its own lanes, chunk k+1 dispatched before chunk k is decoded."""
    n_dev = int(mesh.devices.size) if mesh is not None else 1

    violated_parts: List[np.ndarray] = []
    deadlocked_parts: List[np.ndarray] = []
    vstep_parts: List[np.ndarray] = []
    steps_parts: List[np.ndarray] = []
    occ_num = occ_den = 0  # chunked occupancy estimate (see BatchResult)
    cov_parts: List[tuple] = []  # (bitmap, occ_fired, hiwater, transitions)
    state: Optional[SimState] = None
    totals: Dict[str, float] = {}
    weights: Dict[str, int] = {}
    disp_before = sim.dispatch_count
    t_sweep = time.perf_counter()

    def dispatch(off: int):
        """Launch one chunk's sweep. For single-segment runs (max_steps <=
        dispatch_steps) this returns without waiting on results; longer
        runs block only on the engine's tiny inter-segment early-stop
        reduction, with the next segment already enqueued — the device
        stays busy either way (engine.run's speculative early-stop)."""
        part = seeds_arr[off : off + chunk]
        pad = (-part.size) % n_dev
        if pad:
            # pad to a device multiple with repeats of the first seed; the
            # padded lanes run normally and are stripped before reporting
            part_in = np.concatenate([part, np.repeat(part[:1], pad)])
        else:
            part_in = part
        with telemetry.span("dispatch", site="run_batch", off=off,
                            lanes=part_in.size):
            st = sim.run(
                part_in, max_steps=workload.max_steps,
                dispatch_steps=dispatch_steps, mesh=mesh,
            )
            rerun = (
                sim.run(
                    part_in, max_steps=workload.max_steps,
                    dispatch_steps=dispatch_steps, mesh=mesh,
                )
                if check_determinism else None
            )
        return off, part.size, pad, st, rerun

    def decode(entry) -> None:
        """Read one chunk's small outputs and fold them into the totals
        (this is where the host blocks on device results)."""
        with telemetry.span("decode", site="run_batch", off=entry[0]):
            _decode(entry)

    def _decode(entry) -> None:
        nonlocal state
        off, size, pad, st, rerun = entry
        with telemetry.span("wait", site="run_batch"):
            jax.block_until_ready(st)
        if rerun is not None:
            _assert_runs_bitwise_equal(
                st, rerun, f"seeds[{off}:{off + size}]"
            )
        if pad:
            st = jax.tree_util.tree_map(lambda x: x[:size], st)
        nonlocal occ_num, occ_den
        state = st
        violated_parts.append(np.asarray(st.violated))
        deadlocked_parts.append(np.asarray(st.deadlocked))
        vstep_parts.append(np.asarray(st.violation_step))
        chunk_steps = np.asarray(st.steps)
        steps_parts.append(chunk_steps)
        occ_num += int(chunk_steps.astype(np.int64).sum())
        occ_den += int(chunk_steps.max(initial=0)) * chunk_steps.shape[0]
        if coverage:
            cov_parts.append((
                np.asarray(st.cov.bitmap, np.uint32),
                None if st.occ_fired is None
                else np.asarray(st.occ_fired, np.uint32),
                np.asarray(st.cov.hiwater, np.int32),
                np.asarray(st.cov.transitions, np.int32),
            ))
        s = summarize(st, workload.spec)
        if workload.lane_check is not None:
            # deep host-side oracle: every violating lane + a clean sample
            v = np.nonzero(violated_parts[-1])[0]
            clean = np.nonzero(~violated_parts[-1])[0][: workload.lane_check_sample]
            picked = np.concatenate([v, clean])
            if picked.size:
                with telemetry.span("lane_check", site="run_batch"):
                    checked = workload.lane_check(st, picked)
                for k2, v2 in checked.items():
                    if isinstance(v2, (int, np.integer)):
                        s["lane_check_" + k2] = int(v2)
        for k, v in s.items():
            if not isinstance(v, (int, float)):
                continue
            if k == "first_violation_step":
                # a per-chunk MINIMUM: summing chunk minima would fabricate
                # a step index no lane violated at
                totals[k] = min(totals.get(k, v), v)
            elif k == "coverage_hiwater":
                # a per-chunk MAXIMUM (pool-occupancy high water)
                totals[k] = max(totals.get(k, v), v)
            elif k.startswith("mean_"):
                # lane-weighted average across chunks, not a sum of means
                totals[k] = totals.get(k, 0) + v * size
                weights[k] = weights.get(k, 0) + size
            else:
                totals[k] = totals.get(k, 0) + v

    # double-buffered chunk loop: one chunk in flight on device while the
    # host decodes its predecessor (decode always returns None — every
    # chunk is aggregated; no early exit)
    pipelined(
        range(0, seeds_arr.size, chunk), dispatch, decode,
        serial=not pipeline,
    )
    for k, w in weights.items():
        totals[k] = totals[k] / w
    sweep_dispatches = sim.dispatch_count - disp_before
    sweep_ms = (time.perf_counter() - t_sweep) * 1e3

    violated = np.concatenate(violated_parts)
    deadlocked = np.concatenate(deadlocked_parts)
    # GLOBAL violation lane indices (summarize's are chunk-local; correlating
    # those against the global seeds array mislabels lanes on chunked runs)
    totals["violation_lanes"] = np.nonzero(violated)[0].tolist()[:32]
    totals["n_devices"] = n_dev
    # the chunk plan that ran: lanes a dispatch (before device padding)
    # and dispatches in the call
    totals["chunk"] = min(chunk, seeds_arr.size)
    totals["chunks"] = -(-seeds_arr.size // chunk)
    # chaos-coverage report: every enabled fault clause should fire
    # somewhere in a batch this size; a zero is a dead clause
    from .nemesis import coverage_report, enabled_fire_kinds

    if enabled_fire_kinds(sim.config):
        totals["chaos_coverage"] = coverage_report(totals, sim.config)
    totals["dispatches"] = sweep_dispatches
    totals["wall_ms"] = round(sweep_ms, 3)
    cov = None
    if coverage:
        cov = LaneCoverage(
            bitmap=np.concatenate([p[0] for p in cov_parts]),
            occ_fired=(
                None if cov_parts[0][1] is None
                else np.concatenate([p[1] for p in cov_parts])
            ),
            hiwater=np.concatenate([p[2] for p in cov_parts]),
            transitions=np.concatenate([p[3] for p in cov_parts]),
        )
        # the union count over ALL lanes (summarize's per-chunk counts sum
        # bits that chunks may share; the union is the explorer's currency)
        totals["coverage_bits"] = cov.union_bits()
    occupancy = occ_num / occ_den if occ_den else 1.0
    totals["occupancy"] = round(occupancy, 4)
    result = BatchResult(
        seeds=seeds_arr,
        violated=violated,
        deadlocked=deadlocked,
        summary=totals,
        state=state,
        workload=workload,
        coverage=cov,
        dispatches=sweep_dispatches,
        wall_ms=sweep_ms,
        occupancy=occupancy,
        retired_step=np.concatenate(steps_parts),
        violation_step=np.concatenate(vstep_parts),
    )

    return _post_sweep(
        result, sim, workload, shrink_on_violation, shrink_kwargs,
        max_traces, repro_on_host, max_host_repros,
    )


def _post_sweep(
    result: BatchResult,
    sim: BatchedSim,
    workload: BatchWorkload,
    shrink_on_violation: bool,
    shrink_kwargs: Optional[Dict[str, Any]],
    max_traces: int,
    repro_on_host: bool,
    max_host_repros: int,
) -> BatchResult:
    """The shared post-sweep tail of run_batch's chunked and refill
    paths: auto-triage, violation traces, host repros."""
    if result.violations and shrink_on_violation:
        # auto-triage: ddmin the FIRST violating seed into a minimal repro
        # bundle (a handful of extra device dispatches; see triage.py).
        # raise_on_violation and batch_test then report the bundle path.
        # A triage failure must never eat the primary result — which seeds
        # violated — so it degrades to a warning and the normal report.
        try:
            with telemetry.span("shrink", site="run_batch"):
                result.shrink(**(shrink_kwargs or {}))
        except Exception as e:  # noqa: BLE001 - opt-in convenience step
            import warnings

            warnings.warn(
                f"shrink_on_violation failed ({type(e).__name__}: {e}); "
                "reporting the unshrunken violation",
                stacklevel=2,
            )

    if result.violations and max_traces > 0:
        # device-side microscope: re-run violating seeds with event capture
        # (same jitted step fn => bit-identical trajectory to the batch lane)
        from .trace import trace_seed

        for seed in result.violating_seeds[:max_traces]:
            with telemetry.span("trace", site="run_batch", seed=seed):
                result.traces[seed] = trace_seed(
                    sim, seed, max_steps=workload.max_steps,
                    kind_names=workload.spec.msg_kind_names,
                )

    if telemetry.enabled():
        # observe-only: the sweep above is already finished — this reads
        # host-side numbers (and the traced TraceEvent streams) only
        telemetry.record_batch_result(result, workload=workload.spec.name)
        tdir = telemetry.out_dir()
        if tdir is not None:
            for seed, events in result.traces.items():
                telemetry.write_perfetto(
                    os.path.join(
                        tdir,
                        f"{workload.spec.name}-seed{seed}.perfetto.json",
                    ),
                    events, n_nodes=workload.spec.n_nodes,
                    label=f"{workload.spec.name} seed {seed}",
                )

    if repro_on_host and workload.host_repro is not None and result.violations:
        for seed in result.violating_seeds[:max_host_repros]:
            try:
                result.host_repros[seed] = workload.host_repro(seed)
            except BaseException as e:  # noqa: BLE001 - a raising repro IS a repro
                result.host_repros[seed] = e
    return result


def _run_batch_refill(
    seeds_arr: np.ndarray,
    workload: BatchWorkload,
    sim: BatchedSim,
    lanes: int,
    chunk: int,
    mesh: Optional[Any],
    pipeline: bool,
    coverage: bool,
    check_determinism: bool,
    repro_on_host: bool,
    max_host_repros: int,
    max_traces: int,
    shrink_on_violation: bool,
    shrink_kwargs: Optional[Dict[str, Any]],
    dispatch_steps: int = DEFAULT_DISPATCH_STEPS,
) -> BatchResult:
    """run_batch's continuously batched sweep: each `chunk` of seeds is
    one device-resident queue SEGMENT run by engine.run_refill over
    `lanes` lanes — or, with a mesh, by engine.run_refill_sharded over
    `lanes` lanes PER DEVICE with the chunk's seeds partitioned into
    per-device sub-queues (docs/multichip.md) — while the host tops up
    the queue with the next segment through the same double-buffered
    `pipelined` loop the chunked path uses. Decode reads the
    per-admission result rows in admission (= seed) order, so every
    per-seed output is bit-identical to the chunked sweep's row for
    that seed, whatever the mesh."""
    from .engine import (
        refill_results, refill_results_sharded, summarize_refill,
    )

    if lanes < 1:
        raise ValueError(f"refill lane count must be >= 1, got {lanes}")
    n_dev = int(mesh.devices.size) if mesh is not None else 1
    res_parts: List[dict] = []
    totals: Dict[str, float] = {}
    weights: Dict[str, int] = {}
    occ_num = occ_den = 0
    dev_busy = [0] * n_dev
    dev_total = [0] * n_dev
    state: Optional[SimState] = None
    disp_before = sim.dispatch_count
    t_sweep = time.perf_counter()

    def run_part(part: np.ndarray):
        if mesh is not None:
            return sim.run_refill_sharded(
                part, lanes=lanes, mesh=mesh,
                max_steps=workload.max_steps,
                dispatch_steps=dispatch_steps,
            )
        return sim.run_refill(
            part, lanes=lanes, max_steps=workload.max_steps,
            dispatch_steps=dispatch_steps,
        )

    def dispatch(off: int):
        part = seeds_arr[off : off + chunk]
        with telemetry.span("dispatch", site="run_batch_refill", off=off):
            st = run_part(part)
            rerun = run_part(part) if check_determinism else None
        return off, part.size, st, rerun

    def decode(entry) -> None:
        with telemetry.span("decode", site="run_batch_refill",
                            off=entry[0]):
            _decode(entry)

    def _decode(entry) -> None:
        nonlocal state, occ_num, occ_den
        off, size, st, rerun = entry
        with telemetry.span("wait", site="run_batch_refill"):
            jax.block_until_ready(st)
        if rerun is not None:
            _assert_runs_bitwise_equal(
                st, rerun, f"seeds[{off}:{off + size}] (refill)"
            )
        state = st
        if mesh is not None:
            res = refill_results_sharded(st, admissions=size)
            for d, row in enumerate(res["per_device"]):
                dev_busy[d] += row["busy_lane_steps"]
                dev_total[d] += row["total_lane_steps"]
        else:
            res = refill_results(st)
        res_parts.append(res)
        occ_num += res["busy_lane_steps"]
        occ_den += res["total_lane_steps"]
        s = summarize_refill(res)
        for k, v in s.items():
            if not isinstance(v, (int, float)):
                continue
            if k == "first_violation_step":
                totals[k] = min(totals.get(k, v), v)
            elif k in ("coverage_hiwater",):
                totals[k] = max(totals.get(k, v), v)
            elif k == "occupancy":
                continue  # exact busy/total ratio set after the loop
            elif k.startswith("mean_"):
                totals[k] = totals.get(k, 0) + v * size
                weights[k] = weights.get(k, 0) + size
            else:
                totals[k] = totals.get(k, 0) + v

    pipelined(
        range(0, seeds_arr.size, chunk), dispatch, decode,
        serial=not pipeline,
    )
    for k, w in weights.items():
        totals[k] = totals[k] / w
    sweep_dispatches = sim.dispatch_count - disp_before
    sweep_ms = (time.perf_counter() - t_sweep) * 1e3

    violated = np.concatenate([r["violated"] for r in res_parts])
    deadlocked = np.concatenate([r["deadlocked"] for r in res_parts])
    occupancy = occ_num / occ_den if occ_den else 1.0
    totals["violation_lanes"] = np.nonzero(violated)[0].tolist()[:32]
    totals["n_devices"] = n_dev
    totals["occupancy"] = round(occupancy, 4)
    totals["refill_lanes"] = lanes
    if mesh is not None:
        totals["per_device_occupancy"] = [
            round(dev_busy[d] / max(dev_total[d], 1), 4)
            for d in range(n_dev)
        ]
    from .nemesis import coverage_report, enabled_fire_kinds

    if enabled_fire_kinds(sim.config):
        totals["chaos_coverage"] = coverage_report(totals, sim.config)
    totals["dispatches"] = sweep_dispatches
    totals["wall_ms"] = round(sweep_ms, 3)
    cov = None
    if coverage:
        cov = LaneCoverage(
            bitmap=np.concatenate([r["cov_bitmap"] for r in res_parts]),
            occ_fired=(
                None if res_parts[0]["occ_fired"] is None
                else np.concatenate([r["occ_fired"] for r in res_parts])
            ),
            hiwater=np.concatenate([r["cov_hiwater"] for r in res_parts]),
            transitions=np.concatenate(
                [r["cov_transitions"] for r in res_parts]
            ),
        )
        totals["coverage_bits"] = cov.union_bits()
    result = BatchResult(
        seeds=seeds_arr,
        violated=violated,
        deadlocked=deadlocked,
        summary=totals,
        state=state,
        workload=workload,
        coverage=cov,
        dispatches=sweep_dispatches,
        wall_ms=sweep_ms,
        occupancy=occupancy,
        retired_step=np.concatenate([r["retired"] for r in res_parts]),
        violation_step=np.concatenate(
            [r["violation_step"] for r in res_parts]
        ),
    )
    return _post_sweep(
        result, sim, workload, shrink_on_violation, shrink_kwargs,
        max_traces, repro_on_host, max_host_repros,
    )


def batch_test(
    workload: BatchWorkload,
    default_num: int = 1024,
    expect_violations: bool = False,
    shrink_on_violation: bool = False,
    shrink_kwargs: Optional[Dict[str, Any]] = None,
):
    """Decorator: run the env-configured seed range as ONE device batch.

    Reads the same env vars as `@madsim_test` / the reference's
    `Builder::from_env` (runtime/builder.rs:55-107):

        MADSIM_TEST_SEED               first seed (default 0)
        MADSIM_TEST_NUM                seeds to sweep (one batch)
        MADSIM_TEST_TIME_LIMIT         virtual-time limit in seconds
                                       (overrides the workload's horizon)
        MADSIM_TEST_CONFIG             path to a TOML file whose keys are
                                       SimConfig fields (loss_rate,
                                       latency_*, chaos knobs, ...)
        MADSIM_TEST_CHECK_DETERMINISM  run every chunk twice + compare

    (MADSIM_TEST_JOBS is host-harness-only: the device sweep IS the
    parallelism.) The decorated function receives the BatchResult; when
    `expect_violations` is False, any violation raises BatchViolation with
    repro seeds (and host repro results attached, if the workload has a
    host face).

        @batch_test(raft_workload())
        def test_fuzz(result): ...             # 1024 seeds, one batch
        MADSIM_TEST_NUM=10000 pytest ...       # 10k seeds, one batch
    """

    def deco(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            env = os.environ
            first = int(env.get("MADSIM_TEST_SEED", "0"))
            num = int(env.get("MADSIM_TEST_NUM", str(default_num)))
            check = env.get("MADSIM_TEST_CHECK_DETERMINISM", "") in (
                "1", "true", "TRUE",
            )
            wl = workload
            overrides: Dict[str, Any] = {}
            if "MADSIM_TEST_TIME_LIMIT" in env:
                overrides["horizon_us"] = int(
                    float(env["MADSIM_TEST_TIME_LIMIT"]) * 1e6
                )
            if "MADSIM_TEST_CONFIG" in env:
                from .spec import simconfig_dict_from_toml

                with open(env["MADSIM_TEST_CONFIG"], encoding="utf-8") as f:
                    overrides.update(simconfig_dict_from_toml(
                        f.read(), context="MADSIM_TEST_CONFIG"
                    ))
            if overrides:
                wl = dataclasses.replace(
                    wl,
                    config=dataclasses.replace(
                        wl.config or SimConfig(), **overrides
                    ),
                )
            result = run_batch(
                range(first, first + num), wl, check_determinism=check,
                shrink_on_violation=shrink_on_violation,
                shrink_kwargs=shrink_kwargs,
            )
            if not expect_violations:
                # the raised BatchViolation carries the single-seed repro
                # command (env + pytest node id) and, when shrinking ran,
                # the bundle path + replay one-liner
                result.raise_on_violation()
            return fn(result, *args, **kwargs)

        # pytest resolves __wrapped__'s signature and would demand a fixture
        # named 'result'; advertise the signature minus the injected first
        # parameter so the decorated test collects cleanly
        del wrapper.__wrapped__
        sig = inspect.signature(fn)
        params = list(sig.parameters.values())[1:]
        wrapper.__signature__ = sig.replace(parameters=params)  # type: ignore[attr-defined]
        return wrapper

    return deco
