"""Explorer vs uniform sweep: coverage-per-dispatch and first-bug cost.

The explorer's pitch (docs/explore.md) is that steering lanes toward novel
behavior multiplies bugs-per-execution over the uniform random sweep the
batch path runs today. This bench measures that claim on the SAME two
planted-bug configs benches/ttfb.py sweeps — the deposed-leader re-stamp
under a crash+partition schedule plan, and the chain blind-apply bug under
heavy-tail stragglers — with the same lane budget on both sides:

    uniform:  sequential seeds, `dispatches` chunks of `lanes`, coverage on
    explore:  Explorer(meta_seed=0) — generation 0 IS the uniform sweep's
              first chunk, later generations steer (mutants + swarm)

Reported per config (the acceptance criterion is the dispatch comparison:
the explorer must reach its first violation in no MORE dispatches than the
uniform sweep, and every surfaced violation must carry a ReproBundle):

    coverage_curve          union coverage bits after each dispatch, both
    first_violation_dispatch / wall_to_first_violation_s, both
    coverage_gain_pct       explorer's final union vs uniform's
    violations / bundles    explorer's unique violations + shrunk bundles

Usage: python benches/explore_bench.py [--lanes 256] [--dispatches 8]
Prints one JSON line; bench.py embeds the same rows in BENCH as `explore`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time


def _repo_root_on_path() -> None:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)


_repo_root_on_path()


def uniform_sweep(
    workload, lanes: int, dispatches: int, first_seed: int = 0,
) -> dict:
    """The baseline: sequential seeds in `dispatches` chunks of `lanes`
    with coverage instrumentation on, from a cold sim (the explorer pays
    its compiles inside its own wall number, so the baseline does too).
    Tracks the union coverage curve and the first violating dispatch."""
    import numpy as np

    from madsim_tpu.explore import popcount_rows
    from madsim_tpu.tpu.engine import BatchedSim, COV_WORDS

    t0 = time.perf_counter()
    sim = BatchedSim(workload.spec, workload.config, coverage=True)
    union = np.zeros((COV_WORDS,), np.uint32)
    curve = []
    first_violation = None
    wall_first = None
    for d in range(dispatches):
        seeds = np.arange(
            first_seed + d * lanes, first_seed + (d + 1) * lanes,
            dtype=np.uint32,
        )
        st = sim.run(seeds, max_steps=workload.max_steps)
        violated = np.asarray(st.violated)
        union |= np.bitwise_or.reduce(
            np.asarray(st.cov.bitmap, np.uint32), axis=0
        )
        curve.append(int(popcount_rows(union)))
        if first_violation is None and violated.any():
            first_violation = d
            wall_first = time.perf_counter() - t0
    return {
        "lanes": lanes,
        "dispatches": dispatches,
        "coverage_curve": curve,
        "coverage_bits": curve[-1] if curve else 0,
        "first_violation_dispatch": first_violation,
        "wall_to_first_violation_s": (
            round(wall_first, 3) if wall_first is not None else None
        ),
        "wall_s": round(time.perf_counter() - t0, 3),
    }


def explore_vs_uniform(
    workload, lanes: int = 256, dispatches: int = 8, meta_seed: int = 0,
    shrink: bool = True, max_shrinks: "int | None" = 8,
    out_dir: "str | None" = None,
) -> dict:
    """One config's comparison row. Both sides run cold with the same
    lane x dispatch budget; the uniform side runs first so its compile
    warms nothing the explorer reuses unfairly (the explorer compiles its
    own triage+coverage program — a strictly BIGGER step)."""
    from madsim_tpu.explore import Explorer

    uni = uniform_sweep(workload, lanes, dispatches)

    if out_dir is None and shrink:
        out_dir = tempfile.mkdtemp(prefix="explore_bundles_")
    t0 = time.perf_counter()
    # the planted bugs are seed-DENSE (every violating lane would cost ~10
    # ddmin dispatches), so the bench caps bundles at `max_shrinks`; the
    # bundle-per-violation capability itself is pinned by tests/test_explore
    ex = Explorer(
        workload, meta_seed=meta_seed, lanes=lanes,
        shrink_violations=shrink, max_shrinks=max_shrinks,
        shrink_kwargs={"out_dir": out_dir} if out_dir else None,
    )
    rep = ex.run(dispatches)
    wall = time.perf_counter() - t0

    bundles = sum(1 for v in rep.violations if v.get("bundle_path"))
    row = {
        "uniform": uni,
        "explore": {
            "lanes": lanes,
            "dispatches": dispatches,
            "meta_seed": meta_seed,
            "coverage_curve": rep.coverage_curve,
            "coverage_bits": rep.coverage_bits,
            "corpus_size": rep.corpus_size,
            "first_violation_dispatch": rep.first_violation_dispatch,
            "violations": len(rep.violations),
            "bundles": bundles,
            "wall_s": round(wall, 3),
        },
    }
    if uni["coverage_bits"]:
        row["coverage_gain_pct"] = round(
            100.0 * (rep.coverage_bits - uni["coverage_bits"])
            / uni["coverage_bits"], 1,
        )
    if (
        uni["first_violation_dispatch"] is not None
        and rep.first_violation_dispatch is not None
    ):
        # positive = explorer needed FEWER dispatches (the acceptance bar
        # is >= 0: generation 0 is the uniform sweep's first chunk, so the
        # explorer can never lose on a first-chunk-dense bug and must win
        # or tie on the rest)
        row["dispatch_advantage"] = (
            uni["first_violation_dispatch"] - rep.first_violation_dispatch
        )
    return row


def devloop_ab(
    workload, lanes: int = 16, gens: int = 4, window: int = 2,
    meta_seed: int = 0, seen_cap: int = 1 << 12,
) -> dict:
    """Host loop vs device-resident loop (r19, docs/explore.md) on ONE
    shared sim: the same search run both ways, reporting the
    hardware-independent dispatch economics —

      * host syncs (blocking decodes): 1/generation on the host loop
        (`refill_results`) vs 1/WINDOW on the device loop
        (`devloop_results`, `syncs_per_gen <= 1` by construction);
      * device dispatches (init + segments + early-stop reductions,
        `sim.dispatch_count`): the device loop runs whole windows as one
        chain, so its total is strictly below the host loop's;
      * `generations_per_s`, warm (each side runs once cold for compile,
        then once timed) — wall follows the sync count once the host
        round-trip dominates, so on CPU this is a sanity number, on TPU
        the claim;

    and `fingerprint_match`: the two faces' reports must be
    bit-identical (the tentpole's acceptance contract)."""
    from madsim_tpu.explore import Explorer
    from madsim_tpu.tpu import engine as eng
    from madsim_tpu.tpu.engine import BatchedSim, make_devloop_plan

    plan = make_devloop_plan(
        workload.config, pop=lanes, top_k=16, seen_cap=seen_cap,
    )
    sim = BatchedSim(
        workload.spec, workload.config, triage=True, coverage=True,
        devloop=plan,
    )

    def run(device: bool) -> dict:
        decodes = [0]
        real_r, real_d = eng.refill_results, eng.devloop_results

        def counted(real):
            def f(st):
                decodes[0] += 1
                return real(st)
            return f

        eng.refill_results = counted(real_r)
        eng.devloop_results = counted(real_d)
        try:
            ex = Explorer(
                workload, meta_seed=meta_seed, lanes=lanes, chunk=lanes,
                shrink_violations=False, seen_cap=seen_cap, sim=sim,
                device_loop=device, device_window=window,
            )
            d0 = sim.dispatch_count
            t0 = time.perf_counter()
            rep = ex.run(gens)
            wall = time.perf_counter() - t0
        finally:
            eng.refill_results, eng.devloop_results = real_r, real_d
        return {
            "dispatches": sim.dispatch_count - d0,
            "syncs": decodes[0],
            "syncs_per_gen": round(decodes[0] / gens, 3),
            "generations_per_s": round(gens / max(wall, 1e-9), 2),
            "wall_s": round(wall, 3),
            "fingerprint": rep.fingerprint(),
        }

    run(False), run(True)  # cold pass: compiles land outside the timing
    host, dev = run(False), run(True)
    fp_match = host.pop("fingerprint") == dev.pop("fingerprint")
    return {
        "lanes": lanes,
        "generations": gens,
        "window": window,
        "host": host,
        "device": dev,
        "fingerprint_match": fp_match,
        "dispatch_ratio": round(
            host["dispatches"] / max(dev["dispatches"], 1), 2
        ),
    }


def explore_all(
    lanes: int = 256, dispatches: int = 8, meta_seed: int = 0,
    shrink: bool = True, max_shrinks: "int | None" = 8,
) -> dict:
    """Both planted-bug configs (shared with benches/ttfb.py)."""
    import ttfb

    rows = {}
    for name, (factory, _host) in ttfb.PLANTED.items():
        try:
            rows[name] = explore_vs_uniform(
                factory(), lanes=lanes, dispatches=dispatches,
                meta_seed=meta_seed, shrink=shrink,
                max_shrinks=max_shrinks,
            )
        except Exception as e:  # noqa: BLE001 - one bad config must not
            # hide the other's number
            rows[name] = {"error": f"{type(e).__name__}: {str(e)[:160]}"}
    return rows


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--lanes", type=int, default=256)
    parser.add_argument("--dispatches", type=int, default=8)
    parser.add_argument("--meta-seed", type=int, default=0)
    parser.add_argument("--no-shrink", action="store_true")
    parser.add_argument("--max-shrinks", type=int, default=8)
    parser.add_argument(
        "--devloop", action="store_true",
        help="run the host-vs-device generation-loop A/B instead "
        "(dispatch counts, syncs/gen, generations/s — docs/explore.md)",
    )
    parser.add_argument("--window", type=int, default=2)
    args = parser.parse_args()
    if args.devloop:
        import ttfb

        factory, _ = ttfb.PLANTED["raft_restamp"]
        print(
            json.dumps(devloop_ab(
                factory(), lanes=args.lanes, gens=args.dispatches,
                window=args.window, meta_seed=args.meta_seed,
            )),
            flush=True,
        )
        return
    print(
        json.dumps(explore_all(
            args.lanes, args.dispatches, meta_seed=args.meta_seed,
            shrink=not args.no_shrink, max_shrinks=args.max_shrinks,
        )),
        flush=True,
    )


if __name__ == "__main__":
    main()
