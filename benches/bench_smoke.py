"""bench-smoke: a <60s-per-workload micro-bench for CI and the tier-1 tier.

The full bench.py needs a real accelerator, tens of minutes, and a quiet
machine; regressions in the SWEEP MACHINERY (eager init, per-chunk
recompiles, a dispatch storm like the r5 ~1.4 s/sweep bug) don't need any
of that to show up — they show up in the DISPATCH COUNT, which is
platform-independent and contention-proof. Each workload runs a tiny
sweep (64 lanes, ~0.6 virtual seconds) through the production run_batch
path and asserts:

  * completion with zero violations (the clean specs stay clean),
  * zero pool overflow (the zero-drop discipline at smoke scale),
  * the dispatch budget: init + one sweep segment = 2 device program
    launches per chunk, exactly (BatchResult.dispatches),
  * the LAYOUT budget (r8, docs/state_layout.md): per-workload carry
    bytes per lane (platform-independent — pure dtype x shape) and the
    bytes-per-step estimate over the carry floor. A narrowed field
    silently widening, a bool plane un-packing, or cold state leaking
    back into per-step traffic fails HERE, not three PRs later in a
    BENCH regression.

It NEVER asserts wall-clock — that is bench.py's job, on real hardware,
with the fresh-seed/median discipline. Wall times are printed for eyes
only.

Usage: python benches/bench_smoke.py  (or `make bench-smoke`)
Exit code != 0 on any assertion failure; prints one JSON line.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LANES = 64
VIRTUAL_SECS = 0.6
MAX_STEPS = 2_500  # < dispatch_steps (10k): the sweep must be ONE segment

# r8 layout budgets (docs/state_layout.md). carry_bytes_per_lane is the
# while_loop carry (hot + cold) at THIS smoke config — pure dtype x shape,
# so identical on every backend; measured values (see docs) get ~10%
# headroom for benign drift. est_over_floor bounds the step's estimated
# HBM traffic against the carry's unavoidable read+write: measured
# 3.1-4.6x on the CPU backend (TPU fuses tighter) — 6.0 catches the big
# regressions (cold state re-materializing per step costs ~+1x floor,
# donation loss ~+1x) without flaking on backend variance.
CARRY_BUDGET_B_PER_LANE = {
    "raft": 3520,
    "kv": 6880,
    "twopc": 1710,
    "paxos": 1540,
    "chain": 1670,
}
EST_OVER_FLOOR_MAX = 6.0

# r12 lineage-plane budget (docs/causality.md): with lineage=True the
# carry gains per-node Lamport clocks, the per-lane eid counter, and ONE
# u16 sent_eid stamp per pool slot — measured 3.9% (raft) to 10.3%
# (paxos, the smallest carry) at this smoke config. The 15% ceiling is
# the acceptance bar: a u32 stamp (or a second stamp plane) blows it on
# paxos/twopc, which is exactly the regression this guards. Lineage OFF
# must cost zero bytes — pinned structurally in test_state_layout.py.
LINEAGE_OVERHEAD_PCT_MAX = 15.0


def workloads():
    from madsim_tpu.tpu import chain_workload, raft_workload
    from madsim_tpu.tpu.kv import kv_workload
    from madsim_tpu.tpu.paxos import paxos_workload
    from madsim_tpu.tpu.twopc import twopc_workload

    return {
        "raft": raft_workload(virtual_secs=VIRTUAL_SECS),
        "kv": kv_workload(virtual_secs=VIRTUAL_SECS),
        "twopc": twopc_workload(virtual_secs=VIRTUAL_SECS),
        "paxos": paxos_workload(virtual_secs=VIRTUAL_SECS),
        "chain": chain_workload(virtual_secs=VIRTUAL_SECS),
    }


def layout_budget(name: str, wl) -> dict:
    """The bytes budget: carry bytes/lane (exact) + est_over_floor (XLA
    buffer-assignment estimate of the sweep-loop body vs 2x carry)."""
    import jax.numpy as jnp

    import roofline as rl
    from madsim_tpu.tpu.engine import BatchedSim

    sim = BatchedSim(wl.spec, wl.config)
    st = sim.init(jnp.arange(LANES, dtype=jnp.uint32))
    cb = rl.carry_bytes(st)
    carry = cb["hot_bytes"] + cb["cold_bytes"]
    mem = rl.mem_bytes_per_step(sim, st)
    # lineage-plane carry cost: same config, lineage=True (pure
    # dtype x shape accounting — no run, no compile)
    sim_lin = BatchedSim(wl.spec, wl.config, lineage=True)
    st_lin = sim_lin.init(jnp.arange(LANES, dtype=jnp.uint32))
    cb_lin = rl.carry_bytes(st_lin)
    carry_lin = cb_lin["hot_bytes"] + cb_lin["cold_bytes"]
    lin_pct = round(100.0 * (carry_lin - carry) / carry, 2)
    row = {
        "carry_bytes_per_lane": round(carry / LANES, 1),
        "bytes_per_step": mem["bytes_per_step"],
        "est_over_floor": round(mem["bytes_per_step"] / (2 * carry), 2),
        "lineage_carry_bytes_per_lane": round(carry_lin / LANES, 1),
        "lineage_overhead_pct": lin_pct,
    }
    errors = []
    if lin_pct > LINEAGE_OVERHEAD_PCT_MAX:
        errors.append(
            f"lineage plane widened: +{lin_pct}% carry bytes/lane > "
            f"{LINEAGE_OVERHEAD_PCT_MAX}% budget — the sent_eid stamp "
            "must stay u16 (run tests/test_state_layout.py for the "
            "field name; docs/causality.md)"
        )
    budget = CARRY_BUDGET_B_PER_LANE[name]
    if row["carry_bytes_per_lane"] > budget:
        errors.append(
            f"carry widened: {row['carry_bytes_per_lane']} B/lane > "
            f"budget {budget} — a SimState leaf grew or un-narrowed "
            "(run tests/test_state_layout.py for the field name)"
        )
    if row["est_over_floor"] > EST_OVER_FLOOR_MAX:
        errors.append(
            f"step traffic blew the floor budget: est_over_floor "
            f"{row['est_over_floor']} > {EST_OVER_FLOOR_MAX} — cold/const "
            "state re-entered the per-step carry, or donation broke"
        )
    if errors:
        row["errors"] = errors
    return row


def smoke_one(name: str, wl) -> dict:
    from madsim_tpu.tpu.batch import run_batch

    wl = dataclasses.replace(wl, max_steps=MAX_STEPS, host_repro=None)
    t0 = time.perf_counter()
    # mesh=None: a fixed single-shard layout keeps the dispatch budget
    # exact everywhere (the mesh path adds one device_put per chunk)
    res = run_batch(
        range(LANES), wl, mesh=None, max_traces=0, repro_on_host=False
    )
    wall = time.perf_counter() - t0
    row = {
        "violations": res.violations,
        "overflow": int(res.summary["total_overflow"]),
        "dispatches": res.dispatches,
        "wall_ms": round(res.wall_ms, 1),
        "wall_s": round(wall, 2),  # informational ONLY — never asserted
        "events": int(res.summary["total_events"]),
    }
    errors = []
    if res.violations:
        errors.append(f"{res.violations} violations on a clean spec")
    if row["overflow"]:
        errors.append(f"pool overflow {row['overflow']} at smoke scale")
    # the budget: ONE jitted init + ONE while_loop segment, nothing else.
    # An eager init is dozens of launches; a per-chunk recompile shows up
    # as timeouts; a step-granular loop would be thousands.
    if res.dispatches != 2:
        errors.append(
            f"dispatch budget blown: {res.dispatches} launches per sweep "
            "(expected 2: jitted init + one run segment)"
        )
    if row["events"] <= 0:
        errors.append("no events simulated — the sweep did nothing")
    if errors:
        row["errors"] = errors
    return row


def main() -> int:
    out = {}
    failed = False
    for name, wl in workloads().items():
        row = smoke_one(name, wl)
        row["layout"] = layout_budget(name, wl)
        out[name] = row
        errs = row.get("errors", []) + row["layout"].get("errors", [])
        failed = failed or bool(errs)
    out["ok"] = not failed
    print(json.dumps(out), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
