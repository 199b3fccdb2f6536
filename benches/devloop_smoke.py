"""devloop-smoke: <60s device-resident-search gate for CI (r19).

The device-resident generation loop's value proposition is dispatch
economics, so this smoke asserts the hardware-independent numbers on the
planted raft re-stamp config (the same search run both ways on one
shared sim — benches/explore_bench.devloop_ab):

  * BIT-IDENTITY: the device-loop report fingerprints identically to the
    host loop — corpus, curves, violations (the determinism contract at
    smoke scale; the full matrix lives in tests/test_devloop.py);
  * the SYNC BUDGET: the device loop blocks on the device ONCE PER
    WINDOW (`devloop_results`), so syncs/generation <= 1 — vs the host
    loop's one blocking decode plus upload round-trips every generation;
  * the DISPATCH BUDGET: whole windows run as one dispatch chain, so the
    device loop's total dispatch count (init + segments + early-stop
    reductions) lands strictly below the host loop's for the same
    generations.

Wall times (generations/s) are printed for eyes only — on CPU the sync
savings are noise; on a chip whose host round-trip is long next to a
generation's device time they are the whole point (docs/perf_notes.md
r19; not measured on today's code). Usage:
python benches/devloop_smoke.py  (or `make devloop-smoke`)
Exit code != 0 on any assertion failure; prints one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LANES = 16
GENS = 4
WINDOW = 2


def main() -> None:
    t0 = time.perf_counter()
    import explore_bench
    import ttfb

    factory, _ = ttfb.PLANTED["raft_restamp"]
    row = explore_bench.devloop_ab(
        factory(), lanes=LANES, gens=GENS, window=WINDOW,
    )

    failures = []
    if not row["fingerprint_match"]:
        failures.append(
            "device-loop report fingerprint differs from the host loop "
            "— the determinism contract is broken"
        )
    if row["device"]["syncs_per_gen"] > 1.0:
        failures.append(
            f"device loop blocked {row['device']['syncs']} times for "
            f"{GENS} generations (budget: 1/window = "
            f"{GENS // WINDOW}) — a host round-trip leaked into the "
            "generation boundary?"
        )
    if row["device"]["syncs"] != (GENS + WINDOW - 1) // WINDOW:
        failures.append(
            f"device loop synced {row['device']['syncs']} times, "
            f"expected one per window ({(GENS + WINDOW - 1) // WINDOW})"
        )
    if row["host"]["syncs"] != GENS:
        failures.append(
            f"host loop decoded {row['host']['syncs']} times for "
            f"{GENS} generations — the baseline moved, re-pin the smoke"
        )
    if row["device"]["dispatches"] >= row["host"]["dispatches"]:
        failures.append(
            f"device loop cost {row['device']['dispatches']} dispatches "
            f">= host loop's {row['host']['dispatches']} — the in-jit "
            "boundary is not amortizing"
        )

    out = {
        "devloop": row,
        "wall_s": round(time.perf_counter() - t0, 1),
        "ok": not failures,
        "failures": failures,
    }
    print(json.dumps(out), flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
