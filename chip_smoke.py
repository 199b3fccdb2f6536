"""Bring-up smoke of the batched fuzz engine on a TPU chip.

    python chip_smoke.py             # one chip: the phases below, in order
    python chip_smoke.py --chips 4   # four chips: the multi-chip path only

Drives the main path once through the entry points a user calls
(`run_batch`, triage's shrink, `repro.replay_device`) at the headline
deployment's real size, and fails on any wrong answer:

  device    jax.devices()[0] must be a TPU; there is no CPU path.
  headline  run_batch over 32,768 seeds of 5-node Raft under 10 virtual
            seconds of loss, crash/restart and partition chaos (the
            bench's headline config): zero violations, zero overflow,
            every lane finished, events > 0.
  kv        the linearizability fuzz (BASELINE config 4) over 16,384
            seeds, with the exact host-side Wing-Gong oracle on its
            sampled lanes: zero violations, zero overflow.
  identity  the first 128 headline seeds again on the XLA:CPU device:
            per-seed violated / violation_step / steps / events /
            overflow bit-identical to the chip's rows — the contract
            behind replaying on a laptop a bundle found on the chip.
  triage    the planted deposed-leader bug (benches/ttfb.py) over 8,192
            seeds with shrink_on_violation: a violation found, shrunk to a
            ReproBundle under chiprun_out/chip_smoke/, and replayed at its
            recorded step on the chip and on the CPU device.

`--chips 4` runs run_batch(mesh="auto") over 4 x 32,768 seeds, chunked
and continuously batched (refill), each against mesh=None on device 0:
per-seed rows bit-identical.

The lines before the last are smoke readings (compile and wall seconds,
seeds/s, events/s), labelled with the device kind: not benchmark numbers.
The last line is one JSON object: {"ok": true, "device": {...}}. One
process holds the chip; nothing here starts a child process.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

HEADLINE_LANES = 32_768
KV_LANES = 16_384
IDENTITY_LANES = 128
TRIAGE_SEEDS = 8_192  # benches/ttfb.py's own bound
MULTI_LANES_PER_CHIP = 32_768
MULTI_REFILL_LANES = 8_192  # per chip


class SmokeFailure(AssertionError):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def reading(phase: str, kind: str, **values) -> None:
    print(json.dumps({"smoke_reading": phase, "device_kind": kind, **values}),
          flush=True)


def headline_workload():
    """The bench's headline deployment (bench.py bench_tpu)."""
    from bench import raft_bench_config
    from madsim_tpu.tpu import make_raft_spec
    from madsim_tpu.tpu.batch import BatchWorkload

    return BatchWorkload(
        spec=make_raft_spec(5, client_rate=0.1, log_capacity=16),
        config=raft_bench_config(10.0),
    )


def per_seed_rows(r) -> dict:
    """The per-seed rows of a chunked sweep, as numpy. `events` and
    `overflow` come from the final state, which holds the last chunk's
    lanes only (a chunk is at most run_batch's 65,536 lanes)."""
    import numpy as np

    return {
        "violated": np.asarray(r.violated),
        "violation_step": np.asarray(r.violation_step),
        "steps": np.asarray(r.retired_step),
        "events": np.asarray(r.state.events),
        "overflow": np.asarray(r.state.overflow),
    }


def rows_equal(a: dict, b: dict, what: str) -> None:
    import numpy as np

    for k in a:
        if not np.array_equal(a[k], b[k]):
            bad = np.nonzero(a[k] != b[k])[0]
            i = int(bad[0])
            raise SmokeFailure(
                f"{what}: per-seed {k!r} differs on {bad.size} seeds, first "
                f"at seed index {i}: {a[k][i]} != {b[k][i]}"
            )


def timed_sweeps(seeds_warm, seeds, wl, **kw):
    """Two run_batch calls on one pre-built sim: the first (other seeds)
    compiles, the second is the reading. Returns both results and the
    two walls: (first, second, first_s, second_s)."""
    from madsim_tpu.tpu import BatchedSim
    from madsim_tpu.tpu.batch import run_batch

    sim = BatchedSim(wl.spec, wl.config)
    t0 = time.perf_counter()
    warm = run_batch(seeds_warm, wl, sim=sim, **kw)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    r = run_batch(seeds, wl, sim=sim, **kw)
    wall_s = time.perf_counter() - t0
    return warm, r, cold_s, wall_s


def check_clean_sweep(r, lanes: int, what: str) -> None:
    import numpy as np

    s = r.summary
    require(s["violations"] == 0, f"{what}: {s['violations']} violations")
    require(s["total_overflow"] == 0,
            f"{what}: {s['total_overflow']} messages overflowed the pool")
    require(s.get("lane_check_violations", 0) == 0,
            f"{what}: exact oracle found {s.get('lane_check_violations')}")
    require(s["total_events"] > 0, f"{what}: no events ran")
    require(r.violated.shape == (lanes,), f"{what}: {r.violated.shape} rows")
    # the final state holds the last chunk; the step rows cover them all
    require(bool(np.asarray(r.state.done).all())
            and int(r.retired_step.max()) < r.workload.max_steps,
            f"{what}: not every lane finished")


def phase_headline(kind: str, lanes: int = HEADLINE_LANES):
    wl = headline_workload()
    warm, r, cold_s, wall_s = timed_sweeps(
        range(lanes, 2 * lanes), range(lanes), wl
    )
    check_clean_sweep(warm, lanes, "headline (first sweep)")
    check_clean_sweep(r, lanes, "headline")
    reading(
        "headline_raft5", kind, lanes=lanes, virtual_secs=10.0,
        first_sweep_s=cold_s, wall_s=wall_s, compile_s=cold_s - wall_s,
        seeds_per_s=lanes / wall_s,
        events_per_s=r.summary["total_events"] / wall_s,
        events=r.summary["total_events"], mean_steps=r.summary["mean_steps"],
        overflow=r.summary["total_overflow"],
        violations=r.summary["violations"],
        n_devices=r.summary["n_devices"],
    )
    return wl, r


def phase_kv(kind: str, lanes: int = KV_LANES):
    from madsim_tpu.tpu.kv import kv_workload

    wl = kv_workload(virtual_secs=10.0)
    warm, r, cold_s, wall_s = timed_sweeps(
        range(lanes, 2 * lanes), range(lanes), wl, max_traces=0,
    )
    check_clean_sweep(warm, lanes, "kv (first sweep)")
    check_clean_sweep(r, lanes, "kv")
    s = r.summary
    require(s.get("lane_check_ops_checked", 0) > 0,
            "kv: the exact oracle checked nothing")
    reading(
        "kv_linearizability", kind, lanes=lanes, virtual_secs=10.0,
        first_sweep_s=cold_s, wall_s=wall_s, compile_s=cold_s - wall_s,
        seeds_per_s=lanes / wall_s,
        events_per_s=s["total_events"] / wall_s,
        overflow=s["total_overflow"], violations=s["violations"],
        **{k: v for k, v in s.items() if k.startswith("lane_check_")},
    )


def phase_identity(kind: str, wl, chip_result, lanes: int = IDENTITY_LANES):
    import jax

    from madsim_tpu.tpu.batch import run_batch

    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    with jax.default_device(cpu):
        r = run_batch(range(lanes), wl, mesh=None)
    wall_s = time.perf_counter() - t0
    require(r.state.done.devices() == {cpu},
            f"identity: the reference ran on {r.state.done.devices()}")
    chip = {k: v[:lanes] for k, v in per_seed_rows(chip_result).items()}
    rows_equal(chip, per_seed_rows(r), f"chip vs XLA:CPU ({lanes} seeds)")
    reading("identity_chip_vs_cpu", kind, lanes=lanes, identical=True,
            cpu_wall_s=wall_s, events=int(chip["events"].sum()))


def phase_triage(kind: str, seeds: int = TRIAGE_SEEDS):
    import jax

    from benches.ttfb import restamp_workload
    from madsim_tpu.repro import replay_device
    from madsim_tpu.tpu.batch import run_batch
    from madsim_tpu.triage import ReproBundle

    wl = restamp_workload()
    os.makedirs(OUT_DIR, exist_ok=True)
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        r = run_batch(
            range(seeds), wl, shrink_on_violation=True,
            shrink_kwargs={"out_dir": OUT_DIR}, max_traces=0,
        )
    wall_s = time.perf_counter() - t0
    require(r.violations > 0, f"triage: the planted bug never fired in "
            f"{seeds} seeds")
    require(r.bundle_path is not None and os.path.exists(r.bundle_path),
            "triage: no ReproBundle was written — "
            + "; ".join(str(w.message) for w in caught))
    bundle = ReproBundle.load(r.bundle_path)
    quiet = lambda _msg: None  # noqa: E731
    on_chip = replay_device(bundle, spec=wl.spec, repeats=2, out=quiet)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        on_cpu = replay_device(bundle, spec=wl.spec, repeats=2, out=quiet)
    for where, rep in (("chip", on_chip), ("cpu", on_cpu)):
        require(rep["violated"] and rep["step"] == bundle.violation_step,
                f"triage: {where} replay fired at step {rep['step']}, the "
                f"bundle recorded {bundle.violation_step}")
    reading(
        "triage_find_shrink_replay", kind, seeds=seeds,
        violating_seeds=r.violations, sweep_and_shrink_s=wall_s,
        seed=bundle.seed, violation_step=bundle.violation_step,
        dropped_clauses=bundle.dropped_clauses,
        bundle=os.path.relpath(r.bundle_path, ROOT),
        replayed_on=["chip", "cpu"],
    )


def phase_multichip(kind: str, n_chips: int,
                    lanes_per_chip: int = MULTI_LANES_PER_CHIP,
                    refill_lanes: int = MULTI_REFILL_LANES):
    """run_batch's default mesh over every chip vs mesh=None on device 0,
    chunked and refill: per-seed rows bit-identical."""
    import numpy as np

    from madsim_tpu.tpu import BatchedSim
    from madsim_tpu.tpu.batch import run_batch

    wl = headline_workload()
    sim = BatchedSim(wl.spec, wl.config)
    n = n_chips * lanes_per_chip
    seeds = range(n)

    # run_batch's default chunk (32,768 lanes a chip, at most 65,536 in
    # all: the summary's exact 64-bit sums refuse a larger chunk)
    t0 = time.perf_counter()
    mesh = run_batch(seeds, wl, mesh="auto", sim=sim)
    mesh_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = run_batch(seeds, wl, mesh=None, sim=sim)
    ref_s = time.perf_counter() - t0
    require(mesh.summary["n_devices"] == n_chips,
            f"multichip: mesh ran on {mesh.summary['n_devices']} devices")
    check_clean_sweep(mesh, n, "multichip lane-sharded")
    rows_equal(per_seed_rows(mesh), per_seed_rows(ref),
               f"lane-sharded over {n_chips} chips vs one chip")
    for k in ("total_events", "total_overflow", "mean_steps"):
        require(mesh.summary[k] == ref.summary[k],
                f"lane-sharded over {n_chips} chips vs one chip: {k} "
                f"{mesh.summary[k]} != {ref.summary[k]}")
    reading("multichip_lane_sharded", kind, n_devices=n_chips, seeds=n,
            identical=True, mesh_wall_s=mesh_s, one_chip_wall_s=ref_s,
            events=mesh.summary["total_events"])

    t0 = time.perf_counter()
    fleet = run_batch(seeds, wl, mesh="auto", refill=refill_lanes, chunk=n,
                      sim=sim)
    fleet_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    solo = run_batch(seeds, wl, mesh=None, refill=refill_lanes, chunk=n,
                     sim=sim)
    solo_s = time.perf_counter() - t0
    occ = fleet.summary.get("per_device_occupancy") or []
    require(fleet.summary["n_devices"] == n_chips and len(occ) == n_chips,
            f"multichip refill: n_devices={fleet.summary['n_devices']} "
            f"per_device_occupancy={occ}")
    for k in ("violated", "violation_step", "deadlocked"):
        require(np.array_equal(getattr(fleet, k), getattr(solo, k)),
                f"refill over {n_chips} chips vs one chip: {k} differs")
    for k in ("violations", "total_events", "total_overflow"):
        require(fleet.summary[k] == solo.summary[k],
                f"refill over {n_chips} chips vs one chip: {k} "
                f"{fleet.summary[k]} != {solo.summary[k]}")
    require(fleet.summary["violations"] == 0
            and fleet.summary["total_overflow"] == 0,
            f"multichip refill: {fleet.summary['violations']} violations, "
            f"{fleet.summary['total_overflow']} overflow")
    reading("multichip_refill", kind, n_devices=n_chips, seeds=n,
            refill_lanes_per_chip=refill_lanes, identical=True,
            per_device_occupancy=occ, fleet_wall_s=fleet_s,
            one_chip_wall_s=solo_s, events=fleet.summary["total_events"])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the multi-chip path (four-chip host)")
    args = p.parse_args(argv)

    # the host runtime's optional C++ core builds itself in a child
    # process on first import; the smoke needs none of it
    os.environ.setdefault("MADSIM_NO_NATIVE_BUILD", "1")
    # the identity and replay phases need the CPU backend next to the chip
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax

    dev = jax.devices()[0]
    require(dev.platform == "tpu",
            f"no TPU: JAX's first device is {dev} ({dev.platform})")
    count = len(jax.devices())
    require(count >= args.chips,
            f"--chips {args.chips} needs {args.chips} chips, JAX sees {count}")

    from madsim_tpu.compile_cache import configure_compile_cache

    cache = configure_compile_cache()
    kind = dev.device_kind
    reading("device", kind, platform=dev.platform, count=count,
            compile_cache=os.path.relpath(cache, ROOT)
            if cache.startswith(ROOT) else cache)
    if args.chips == 1:
        wl, headline = phase_headline(kind)
        phase_kv(kind)
        phase_identity(kind, wl, headline)
        phase_triage(kind)
    else:
        phase_multichip(kind, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind, "count": count,
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
