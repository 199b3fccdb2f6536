"""Roofline accounting for the BatchedSim step (VERDICT r4 item 1).

Answers, with measurements rather than assertions:
  1. What is the chip's ATTAINABLE HBM bandwidth (a plain jitted
     read+write streaming kernel, best-of-reps)?
  2. How many bytes does one engine step access (XLA's own cost model on
     the compiled program — counts HBM traffic of every non-fused
     operand/result), and how many bytes is the RESIDENT state pytree?
  3. What fraction of attainable bandwidth does the step achieve, and
     where do the bytes go (ablation attribution: handlers / invariants /
     chaos / pool)?

Usage: python benches/roofline.py [--lanes 32768] [--scan 300]
Prints one JSON line; bench.py embeds the same accounting in BENCH.
"""

from __future__ import annotations

import argparse
import json
import time


def measure_copy_bw_gbs(n_mb: int = 256, reps: int = 3) -> float:
    """Attainable HBM bandwidth by the MARGINAL method: time an on-device
    streaming loop at two loop counts and divide the extra bytes by the
    extra time. Every pitfall here was hit and fixed in round 5:
      * a single-kernel timing measures dispatch (a fixed per-call
        overhead), not bandwidth — hence the loop;
      * `a + 1` loop bodies get algebraically collapsed by XLA into one
        pass — hence the xorshift body;
      * a timed rep must do new work — hence a fresh seed input per rep;
      * the tiny reduced output forces a real readback, so the timing
        ends when the device work does.
    The marginal rate cancels the fixed per-dispatch cost exactly."""
    import jax
    import jax.numpy as jnp

    n = n_mb * (1 << 20) // 4
    L1, L2 = 8, 72

    def make(loops):
        @jax.jit
        def f(seed):
            x = jnp.arange(n, dtype=jnp.uint32) + seed
            y = jax.lax.fori_loop(0, loops, lambda i, a: a ^ (a << 13), x)
            return y[::131072].sum()
        return f

    f1, f2 = make(L1), make(L2)
    int(f1(jnp.uint32(1)))
    int(f2(jnp.uint32(1)))
    rates = []
    for r in range(2, reps + 2):
        t0 = time.perf_counter()
        int(f1(jnp.uint32(r)))
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        int(f2(jnp.uint32(r)))
        t2 = time.perf_counter() - t0
        if t2 > t1:
            rates.append(2 * n * 4 * (L2 - L1) / (t2 - t1) / 1e9)
    if not rates:
        return float("nan")
    # MEDIAN, not max: contention hitting the short-loop rep inflates the
    # marginal rate without bound (one bench run recorded an impossible
    # 2 TB/s); the median of interleaved pairs is robust. Reported as
    # measured: a value above the device's published peak says the reps
    # were contaminated, and hiding it behind a ceiling would not.
    return sorted(rates)[len(rates) // 2]


def compile_sweep_step(sim, state):
    """Compile the program the sweep loop ACTUALLY runs (r8): the
    hot/cold/const split step, with the (hot, cold) carry donated the way
    `_run`'s while_loop aliases it. Accounting bytes for `_step` on the
    flat SimState would charge the loop-invariant ConstState (key0, ctl,
    skew_ppm) as per-step output traffic the real loop no longer pays.

    Memoized per (sim, state shapes): hlo_hbm_bytes, kernel_rows and
    mem_bytes_per_step all walk the SAME compiled program, and on a real
    chip this compile is the dominant roofline cost — it must be paid
    once per (workload, lane count), not once per accounting view."""
    import jax

    from madsim_tpu.tpu.engine import split_state

    key = tuple(
        (leaf.shape, str(leaf.dtype))
        for leaf in jax.tree_util.tree_leaves(state)
    )
    cache = sim.__dict__.setdefault("_sweep_step_compiled", {})
    if key in cache:
        return cache[key]
    hot, cold, const = split_state(state)

    def loop_body(h, c, k):
        # drop the TraceRecord exactly like _run's while_loop body does —
        # XLA dead-code-eliminates the record-only work there, so keeping
        # it here would charge bytes the sweep never moves
        h2, c2, _ = sim._step_split(h, c, k)
        return h2, c2

    step = jax.jit(loop_body, donate_argnums=(0, 1))
    cache[key] = step.lower(hot, cold, const).compile()
    return cache[key]


# shapes like s32[32768,5,70] / pred[32768,70]{...}; tuples handled by
# summing their leaf shapes
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8,
}
# HLO opcodes that are bookkeeping, not kernels (no HBM traffic of their
# own after buffer assignment)
_NON_KERNEL_OPS = (
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
)


def _shape_bytes(shape_str: str) -> int:
    import re

    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        size = 1
        if dims:
            for d in dims.split(","):
                size *= int(d)
        total += size * _DTYPE_BYTES[dt]
    return total


def _entry_lines(txt: str) -> list:
    """The entry computation's instruction lines ("ENTRY %name ... {" to
    its closing brace), stripped."""
    entry = []
    in_entry = False
    for line in txt.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
            continue
        if in_entry:
            if line.startswith("}"):
                break
            entry.append(line.strip())
    return entry


def _entry_kernels(txt: str) -> list:
    """(name, opcode, out_bytes, read_bytes) per top-level kernel of the
    entry computation — the shared parse behind `hlo_hbm_bytes` and
    `kernel_rows`. After XLA fusion each remaining top-level instruction
    is one launched kernel: it reads its named operands from HBM and
    writes its result; fusion-internal values never materialize."""
    import re

    entry = _entry_lines(txt)
    # name -> bytes for all top-level results + parameters (operand reads
    # are charged by name: optimized HLO references operands by name only)
    name_bytes = {}
    for line in entry:
        m = re.match(r"(%?[\w.\-]+) = (\([^)]*\)|[^ ]+) ([\w\-]+)", line)
        if m:
            name_bytes[m.group(1).lstrip("%")] = _shape_bytes(m.group(2))
    kernels = []
    for line in entry:
        m = re.match(
            r"(%?[\w.\-]+) = (\([^)]*\)|[^ ]+) ([\w\-]+)\((.*)\)", line
        )
        if not m:
            continue
        name, shape_str, opcode, operands = m.groups()
        if opcode in _NON_KERNEL_OPS:
            continue
        read_b = sum(
            name_bytes.get(op.group(1), 0)
            for op in re.finditer(r"%([\w.\-]+)", operands)
        )
        kernels.append(
            (name.lstrip("%"), opcode, _shape_bytes(shape_str), read_b)
        )
    return kernels


def hlo_hbm_bytes(sim, state) -> dict:
    """Model REAL HBM traffic from the optimized HLO: after XLA fusion,
    each top-level instruction of the entry computation reads its operands
    from HBM and writes its result to HBM — fusion-internal values never
    materialize. Summing parameter/result buffer sizes of the remaining
    top-level ops is therefore a faithful (slightly conservative: ignores
    cache reuse between adjacent ops) model of bytes moved, unlike
    cost_analysis()['bytes accessed'], which counts every HLO operand as
    if materialized and overcounts several-fold."""
    import collections

    compiled = compile_sweep_step(sim, state)
    kernels = _entry_kernels(compiled.as_text())
    by_op = collections.Counter()
    for _name, opcode, out_b, _read_b in kernels:
        by_op[opcode] += out_b
    traffic = sum(k[2] for k in kernels)
    read_traffic = sum(k[3] for k in kernels)

    mem = compiled.memory_analysis()
    return {
        "hbm_write_bytes": traffic,
        "hbm_read_bytes": read_traffic,
        "hbm_model_bytes": traffic + read_traffic,
        "n_top_level_kernels": len(kernels),
        "top_write_ops": dict(by_op.most_common(8)),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "arg_bytes": getattr(mem, "argument_size_in_bytes", None),
        "out_bytes": getattr(mem, "output_size_in_bytes", None),
    }


def kernel_rows(sim, state, top: int = 12) -> list:
    """PER-FUSED-KERNEL HBM attribution (r13; the BENCH `kernel_rows`
    key): the sweep-step program's top-level kernels ranked by modeled
    HBM bytes (result written + operands read — the `hlo_hbm_bytes`
    traffic model, per kernel), each with its estimated share of step
    TIME. The step is bandwidth-bound (docs/perf_notes.md), so a
    kernel's byte share IS its time share to first order — this is the
    steering table a perf round (or the autotuner's future knob
    proposals) reads to know which fusion to attack next. Kernels below
    the top `top` fold into one "(other)" row so the table stays
    readable; shares always sum to ~100."""
    compiled = compile_sweep_step(sim, state)
    kernels = _entry_kernels(compiled.as_text())
    total = sum(out_b + read_b for _n, _o, out_b, read_b in kernels) or 1
    ranked = sorted(
        kernels, key=lambda k: k[2] + k[3], reverse=True
    )
    rows = []
    for name, opcode, out_b, read_b in ranked[: max(0, int(top))]:
        rows.append({
            "kernel": name,
            "op": opcode,
            "write_bytes": out_b,
            "read_bytes": read_b,
            "bytes": out_b + read_b,
            "time_share_pct": round((out_b + read_b) / total * 100, 2),
        })
    rest = ranked[max(0, int(top)):]
    if rest:
        out_b = sum(k[2] for k in rest)
        read_b = sum(k[3] for k in rest)
        rows.append({
            "kernel": f"(other x{len(rest)})",
            "op": "(other)",
            "write_bytes": out_b,
            "read_bytes": read_b,
            "bytes": out_b + read_b,
            "time_share_pct": round((out_b + read_b) / total * 100, 2),
        })
    return rows


def workload_kernel_rows(sim, lanes: int, top: int = 12) -> list:
    """`kernel_rows` for a workload at a lane count. The attribution is
    a walk of the COMPILED step's HLO text, which depends on state
    shapes only — never on values — so a fresh init suffices (no settle
    steps), and the compile itself is shared with the roofline rows via
    the compile_sweep_step memo."""
    import jax.numpy as jnp

    return kernel_rows(sim, sim.init(jnp.arange(lanes)), top=top)


def state_bytes(state) -> int:
    """Resident bytes of the SimState pytree (the true lower bound on step
    traffic: the carry is read and written every step)."""
    import jax

    return sum(
        x.size * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(state)
    )


def carry_bytes(state) -> dict:
    """Byte breakdown of the r8 sweep-loop split: hot + cold are the
    while_loop carry (read AND written every step — their 2x is the carry
    floor); const is loop-invariant (read-only, never re-emitted)."""
    import jax

    from madsim_tpu.tpu.engine import split_state

    def nbytes(tree):
        return sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(tree)
        )

    hot, cold, const = split_state(state)
    return {
        "hot_bytes": nbytes(hot),
        "cold_bytes": nbytes(cold),
        "const_bytes": nbytes(const),
    }


# honesty interval around the memory-analysis estimate (see
# mem_bytes_per_step): the residual uncertainty after XLA's own buffer
# assignment is pinned down — multi-read args/temps push true traffic up,
# on-chip reuse pulls it down. ±20% gives a 1.5x-wide bracket, vs the r5
# lo/hi pair's 3.7x (buffer-assignment floor vs per-op HLO sum ceiling).
MEM_EST_INTERVAL = 1.2


def mem_bytes_per_step(sim, state) -> dict:
    """HBM bytes per step from XLA's OWN buffer assignment
    (`compiled.memory_analysis()`): arguments are read once, outputs
    written once, temp buffers written then read — est = arg + out +
    2*temp. This replaces the r5 lo/hi bracket (buffer-assignment lower
    bound vs per-op HLO traffic model upper bound, 3.7x apart) with ONE
    estimate plus a single honesty interval: the remaining uncertainty is
    second-order (a temp read by several kernels counts once here; an
    argument streamed through cache may cost less than its size), far
    smaller than the HLO model's systematic double-counting of every
    fusion boundary. The interval is ±20% (bracket 1.44x <= 1.5x), which
    on the r5 headline config comfortably contains the measured
    achieved-bandwidth point."""
    compiled = compile_sweep_step(sim, state)
    mem = compiled.memory_analysis()
    arg = int(getattr(mem, "argument_size_in_bytes", 0) or 0)
    out = int(getattr(mem, "output_size_in_bytes", 0) or 0)
    tmp = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
    est = arg + out + 2 * tmp
    return {
        "arg_bytes": arg,
        "out_bytes": out,
        "temp_bytes": tmp,
        "bytes_per_step": est,
        "bytes_per_step_lo": int(est / MEM_EST_INTERVAL),
        "bytes_per_step_hi": int(est * MEM_EST_INTERVAL),
    }


def workload_sims(lanes: int, virtual_secs: float = 10.0,
                  client_rate: float = 0.1) -> dict:
    """name -> (BatchedSim, lanes, max_steps) for every device workload,
    at the SAME configs bench.py sweeps (the per-workload roofline must
    describe the step the bench actually runs)."""
    import os
    import sys

    try:
        import bench as benchmod
    except ImportError:  # invoked as `python benches/roofline.py`
        sys.path.insert(
            0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        import bench as benchmod
    from madsim_tpu.tpu import BatchedSim, chain_workload, make_raft_spec
    from madsim_tpu.tpu.kv import kv_workload
    from madsim_tpu.tpu.paxos import paxos_workload
    from madsim_tpu.tpu.twopc import twopc_workload

    raft_spec = make_raft_spec(
        n_nodes=5, client_rate=client_rate, log_capacity=16
    )
    raft_cfg = benchmod.raft_bench_config(virtual_secs)
    kv = kv_workload(virtual_secs=virtual_secs)
    tp = twopc_workload(virtual_secs=virtual_secs)
    px = paxos_workload(virtual_secs=virtual_secs)
    ch = chain_workload(virtual_secs=virtual_secs)
    return {
        "raft": (BatchedSim(raft_spec, raft_cfg), lanes,
                 int(virtual_secs * 600) + 2000),
        "kv": (BatchedSim(kv.spec, kv.config), lanes,
               int(virtual_secs * 1200) + 2000),
        "twopc": (BatchedSim(tp.spec, tp.config), lanes,
                  int(virtual_secs * 1600) + 2000),
        "paxos": (BatchedSim(px.spec, px.config), lanes,
                  int(virtual_secs * 1600) + 2000),
        "chain": (BatchedSim(ch.spec, ch.config), lanes,
                  int(virtual_secs * 2400) + 2000),
    }


def workload_roofline_row(sim, lanes: int, bw_gbs: float, scan: int = 300,
                          warm_steps: int = 200, timed: bool = True) -> dict:
    """One per-workload roofline row: resident state bytes, the
    memory-analysis bytes/step estimate (+ honesty interval), and — when
    `timed` — the measured step time with achieved bandwidth and the
    carry floor (state read+write at attainable bandwidth: the step's
    hard lower bound; step_over_floor says how far above it the step
    runs, i.e. how much headroom intermediates still cost)."""
    import jax
    import jax.numpy as jnp

    state = sim.run_steps(sim.init(jnp.arange(lanes)), warm_steps)
    jax.block_until_ready(state)
    mem = mem_bytes_per_step(sim, state)
    sbytes = state_bytes(state)
    cb = carry_bytes(state)
    # the carry floor in BYTES: the while_loop carry (hot + cold) is read
    # and written every step; the loop-invariant const tree is read-only
    # and excluded (r8 — that exclusion is the point of the split)
    floor_bytes = 2 * (cb["hot_bytes"] + cb["cold_bytes"])
    floor_ms = floor_bytes / (bw_gbs * 1e9) * 1e3
    row = {
        "lanes": lanes,
        "state_bytes": sbytes,
        "state_bytes_per_lane": round(sbytes / lanes, 1),
        **cb,
        "bytes_per_step": mem["bytes_per_step"],
        "bytes_per_step_lo": mem["bytes_per_step_lo"],
        "bytes_per_step_hi": mem["bytes_per_step_hi"],
        "carry_floor_bytes": floor_bytes,
        # the layout-budget headline (asserted by bench_smoke): how many
        # times the carry's unavoidable read+write the step's estimated
        # traffic is — 1.0 would mean zero intermediate HBM traffic
        "est_over_floor": round(mem["bytes_per_step"] / floor_bytes, 2),
        "carry_floor_ms": round(floor_ms, 3),
    }
    if timed:
        ms = time_step_ms(sim, state, scan, lanes=lanes,
                          warm_steps=warm_steps)
        row.update({
            "step_ms": round(ms, 3),
            "achieved_gbs": round(
                mem["bytes_per_step"] / (ms / 1e3) / 1e9, 1
            ),
            "pct_of_attainable": round(
                mem["bytes_per_step"] / (ms / 1e3) / 1e9 / bw_gbs * 100, 1
            ),
            # the conservative utilization claim (ISSUE 6 bar): achieved
            # bandwidth computed from the LO-bound bytes estimate
            "pct_of_attainable_lo": round(
                mem["bytes_per_step_lo"] / (ms / 1e3) / 1e9 / bw_gbs * 100,
                1,
            ),
            "step_over_floor": round(ms / floor_ms, 2),
        })
    return row


def per_workload_roofline(lanes: int = 32768, scan: int = 300,
                          timed: bool = True) -> dict:
    """The per-workload roofline table (r6): one row per device workload,
    so 'bandwidth-bound' is a per-workload number and a trailing workload
    shows WHERE it trails (state bytes? bytes/step? utilization?)."""
    bw = measure_copy_bw_gbs()
    rows = {}
    for name, (sim, wl_lanes, _steps) in workload_sims(lanes).items():
        rows[name] = workload_roofline_row(
            sim, wl_lanes, bw, scan=scan, timed=timed
        )
    return {"attainable_hbm_gbs": round(bw, 1), "rows": rows}


def _spread_mix_sim(virtual_secs: float):
    """The 10x-horizon-spread workload mix's sim (shared by
    refill_occupancy and mesh_scaling): raft under a crash+loss plan.
    ONE definition lives in madsim_tpu.tune — the r13 tuner measures the
    same mix these tables report on, so the two can never drift onto
    different workloads."""
    from madsim_tpu.tune import spread_mix_sim

    return spread_mix_sim(virtual_secs)


def _spread_ctl_rows(h):
    """Per-admission TriageCtl rows for a horizon column `h` (int64 us)."""
    from madsim_tpu.tune import spread_ctl_from_h

    return spread_ctl_from_h(h)


def mesh_scaling(
    lanes: int = 16, waves: int = 16, spread: int = 10,
    long_every: int = 8, virtual_secs: float = 1.0,
    device_counts=(1, 2, 4, 8), max_steps: int = 50_000,
) -> dict:
    """The multi-chip fleet's headline table (r10, docs/multichip.md):
    the sharded refill sweep on the 10x horizon-spread mix at 1/2/4/8
    devices with EQUAL per-device lanes and equal per-device queue depth
    (admissions scale with the device count). Per row: seeds/s (wall —
    hardware-dependent), per-device occupancy, and the aggregate
    LANE-STEP THROUGHPUT per sweep iteration (busy-lane-steps / max
    device iters — the hardware-independent scaling number: one device
    caps at `lanes` per iteration, D devices at D * lanes).
    `scaling_vs_1dev` on the D-device row is that number over the
    1-device row's; the multichip smoke asserts >= 6x at D = 8.
    Device counts beyond the visible device count are skipped."""
    import numpy as np

    import jax
    from madsim_tpu.tpu.engine import (
        refill_results, refill_results_sharded,
    )

    sim, horizon = _spread_mix_sim(virtual_secs)
    devs = jax.devices()
    rows = []
    base_tp = None
    for D in device_counts:
        if D > len(devs):
            continue
        A = lanes * waves * D
        seeds = np.arange(A, dtype=np.uint32)
        h = np.where(
            np.arange(A) % long_every == 0, horizon, horizon // spread
        ).astype(np.int64)
        ctl = _spread_ctl_rows(h)
        t0 = time.perf_counter()
        if D == 1:
            st = sim.run_refill(
                seeds, lanes=lanes, max_steps=max_steps, ctl=ctl
            )
            res = refill_results(st)
            per_dev = [{
                "iters": res["iters"],
                "busy_lane_steps": res["busy_lane_steps"],
                "total_lane_steps": res["total_lane_steps"],
                "occupancy": res["occupancy"],
            }]
            tp = res["busy_lane_steps"] / max(res["iters"], 1)
        else:
            mesh = jax.sharding.Mesh(np.array(devs[:D]), ("seeds",))
            st = sim.run_refill_sharded(
                seeds, lanes=lanes, mesh=mesh, max_steps=max_steps,
                ctl=ctl,
            )
            res = refill_results_sharded(st, admissions=A)
            per_dev = res["per_device"]
            tp = res["lane_steps_per_iter"]
        wall_s = time.perf_counter() - t0
        if base_tp is None:
            base_tp = tp
        rows.append({
            "devices": D,
            "admissions": A,
            "lanes_per_device": lanes,
            "seeds_per_sec": round(A / max(wall_s, 1e-9), 1),
            "wall_ms": round(wall_s * 1e3, 1),
            "occupancy": round(float(res["occupancy"]), 4),
            "per_device_occupancy": [
                round(float(p["occupancy"]), 4) for p in per_dev
            ],
            "lane_steps_per_iter": round(tp, 2),
            "scaling_vs_1dev": round(tp / max(base_tp, 1e-9), 2),
        })
    return {
        "horizon_spread": spread,
        "long_every": long_every,
        "visible_devices": len(devs),
        "rows": rows,
    }


def refill_occupancy(
    lanes: int = 256, waves: int = 8, spread: int = 10,
    long_every: int = 8, virtual_secs: float = 2.0,
    max_steps: int = 50_000,
) -> dict:
    """The continuous-batching headline metric (r9): LANE OCCUPANCY —
    busy-lane-steps / total-lane-steps per dispatch — on a synthetic
    workload mix with a `spread`x horizon spread (one long admission per
    `long_every`, the ddmin-probe / short-mutant shape), refill vs the
    chunked path on the SAME admissions. Also reports the lane-step
    advantage: how many total lane-steps the chunked path burns per
    refill lane-step for identical per-seed results (wall-clock-free, so
    the number is hardware-independent; the wall ratio follows it once
    the step is bandwidth-bound). Reported into BENCH by bench.py and
    asserted >= 0.9 occupancy by `make refill-smoke`."""
    import numpy as np

    from madsim_tpu.tpu.engine import refill_results

    sim, horizon = _spread_mix_sim(virtual_secs)
    A = lanes * waves
    seeds = np.arange(A, dtype=np.uint32)
    h = np.where(
        np.arange(A) % long_every == 0, horizon, horizon // spread
    ).astype(np.int64)

    def ctl_rows(sel):
        return _spread_ctl_rows(h[sel])

    all_rows = ctl_rows(np.ones((A,), bool))
    t0 = time.perf_counter()
    d0 = sim.dispatch_count
    st = sim.run_refill(seeds, lanes=lanes, max_steps=max_steps,
                        ctl=all_rows)
    res = refill_results(st)
    refill_ms = (time.perf_counter() - t0) * 1e3
    refill_disp = sim.dispatch_count - d0

    chunk_busy = chunk_total = 0
    t0 = time.perf_counter()
    d0 = sim.dispatch_count
    for off in range(0, A, lanes):
        sel = np.zeros((A,), bool)
        sel[off:off + lanes] = True
        stc = sim.run(seeds[off:off + lanes], max_steps=max_steps,
                      dispatch_steps=max_steps, ctl=ctl_rows(sel))
        steps = np.asarray(stc.steps, np.int64)
        chunk_busy += int(steps.sum())
        chunk_total += int(steps.max(initial=0)) * steps.shape[0]
    chunked_ms = (time.perf_counter() - t0) * 1e3
    chunked_disp = sim.dispatch_count - d0

    return {
        "lanes": lanes,
        "admissions": A,
        "horizon_spread": spread,
        "long_every": long_every,
        "occupancy_refill": round(float(res["occupancy"]), 4),
        "occupancy_chunked": round(chunk_busy / max(chunk_total, 1), 4),
        "busy_lane_steps": res["busy_lane_steps"],
        "total_lane_steps_refill": res["total_lane_steps"],
        "total_lane_steps_chunked": chunk_total,
        # chunked lane-steps burned per refill lane-step, same results
        "lane_step_advantage": round(
            chunk_total / max(res["total_lane_steps"], 1), 2
        ),
        "dispatches_refill": refill_disp,
        "dispatches_chunked": chunked_disp,
        "refill_wall_ms": round(refill_ms, 1),
        "chunked_wall_ms": round(chunked_ms, 1),
    }


def step_cost(sim, state):
    """XLA cost analysis of the compiled single-step program."""
    compiled = compile_sweep_step(sim, state)
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return {
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "flops": float(ca.get("flops", 0.0)),
    }


def time_step_ms(sim, state, scan: int, reps: int = 3, lanes: int = 0,
                 warm_steps: int = 200) -> float:
    """Median per-step ms over `reps` fresh-seed scan chunks, through the
    shared measurement discipline (madsim_tpu.measure.time_scan_ms:
    fresh seeds per rep, the EXACT (shape, scan) program warmed before
    timing). `state` is accepted for caller symmetry; the discipline
    rebuilds its own settled states from the rep index, settled
    `warm_steps` deep — the SAME depth the caller's accounting state
    used, so timing and bytes accounting describe one regime."""
    del state  # the discipline derives every rep's state from its index
    from madsim_tpu.measure import time_scan_ms

    return time_scan_ms(
        sim.init, sim.run_steps, lanes, scan=scan, warm_steps=warm_steps,
        rounds=reps,
    )


def roofline(lanes: int = 32768, scan: int = 300, variants: bool = True) -> dict:
    import dataclasses

    import jax.numpy as jnp

    import bench as benchmod
    from madsim_tpu.tpu import BatchedSim, make_raft_spec
    from madsim_tpu.tpu.spec import Outbox

    spec = make_raft_spec(n_nodes=5, client_rate=0.1)
    cfg = benchmod.raft_bench_config(10.0)
    sim = BatchedSim(spec, cfg)
    state = sim.run_steps(sim.init(jnp.arange(lanes)), 200)

    bw = measure_copy_bw_gbs()
    cost = step_cost(sim, state)
    sbytes = state_bytes(state)
    cb = carry_bytes(state)
    hlo = hlo_hbm_bytes(sim, state)
    mem = mem_bytes_per_step(sim, state)
    ms = time_step_ms(sim, state, scan, lanes=lanes)
    floor_bytes = 2 * (cb["hot_bytes"] + cb["cold_bytes"])

    out = {
        "attainable_hbm_gbs": round(bw, 1),
        "step_ms": round(ms, 3),
        "step_bytes_accessed": cost["bytes_accessed"],
        "step_flops": cost["flops"],
        "state_bytes": sbytes,
        **cb,
        "carry_floor_bytes": floor_bytes,
        "est_over_floor": round(mem["bytes_per_step"] / floor_bytes, 2),
        # the headline estimate: XLA buffer assignment (arg + out +
        # 2*temp) with its +-20% honesty interval; the HLO per-op model
        # below is kept as a diagnostic (it systematically double-counts
        # fusion boundaries — see mem_bytes_per_step)
        "bytes_per_step": mem["bytes_per_step"],
        "bytes_per_step_lo": mem["bytes_per_step_lo"],
        "bytes_per_step_hi": mem["bytes_per_step_hi"],
        "hlo_model": hlo,
        "achieved_gbs": round(
            mem["bytes_per_step"] / (ms / 1e3) / 1e9, 1
        ),
        "pct_of_attainable": round(
            mem["bytes_per_step"] / (ms / 1e3) / 1e9 / bw * 100, 1
        ),
        "pct_of_attainable_lo": round(
            mem["bytes_per_step_lo"] / (ms / 1e3) / 1e9 / bw * 100, 1
        ),
        "arith_intensity_flops_per_byte": round(
            cost["flops"] / max(mem["bytes_per_step"], 1), 3
        ),
    }

    if variants:
        # ablation attribution, bytes AND ms per ablated phase
        def id_on_message(s, nid, src, kind, payload, now, key):
            E = spec.max_out_msg
            return (
                s,
                Outbox(
                    valid=jnp.zeros((E,), jnp.bool_),
                    dst=jnp.zeros((E,), jnp.int32),
                    kind=jnp.zeros((E,), jnp.int32),
                    payload=jnp.zeros((E, spec.payload_width), jnp.int32),
                ),
                jnp.int32(-1),
            )

        def id_on_timer(s, nid, now, key):
            E = spec.max_out
            return (
                s,
                Outbox(
                    valid=jnp.zeros((E,), jnp.bool_),
                    dst=jnp.zeros((E,), jnp.int32),
                    kind=jnp.zeros((E,), jnp.int32),
                    payload=jnp.zeros((E, spec.payload_width), jnp.int32),
                ),
                now + 50_000,
            )

        def id_on_event(s, nid, src, kind, payload, now, key):
            E = spec.max_out
            return (
                s,
                Outbox(
                    valid=jnp.zeros((E,), jnp.bool_),
                    dst=jnp.zeros((E,), jnp.int32),
                    kind=jnp.zeros((E,), jnp.int32),
                    payload=jnp.zeros((E, spec.payload_width), jnp.int32),
                ),
                jnp.where(kind == -1, now + 50_000, jnp.int32(-1)),
            )

        # the ablated trio is internally consistent (same identity
        # behavior); the stale-wrapper guard requires it to be visible
        id_on_message.__wraps_event__ = id_on_event
        id_on_timer.__wraps_event__ = id_on_event

        ablations = {
            "no_handlers": dataclasses.replace(
                spec, on_message=id_on_message, on_timer=id_on_timer,
                on_event=id_on_event,
            ),
            "no_invariants": dataclasses.replace(
                spec,
                check_invariants=lambda ns, alive, now: jnp.bool_(True),
            ),
        }
        for name, aspec in ablations.items():
            asim = BatchedSim(aspec, cfg)
            astate = asim.run_steps(asim.init(jnp.arange(lanes)), 200)
            acost = step_cost(asim, astate)
            ams = time_step_ms(asim, astate, scan, lanes=lanes)
            out[name] = {
                "step_ms": round(ams, 3),
                "bytes_accessed": acost["bytes_accessed"],
                "attrib_ms": round(out["step_ms"] - ams, 3),
                "attrib_bytes": cost["bytes_accessed"] - acost["bytes_accessed"],
            }
    return out


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--lanes", type=int, default=32768)
    parser.add_argument("--scan", type=int, default=300)
    parser.add_argument("--no-variants", action="store_true")
    parser.add_argument(
        "--per-workload", action="store_true",
        help="emit one roofline row per device workload instead of the "
        "headline-raft deep dive",
    )
    parser.add_argument(
        "--occupancy", action="store_true",
        help="emit the continuous-batching lane-occupancy row (refill vs "
        "chunked on a 10x horizon-spread mix) instead of the deep dive",
    )
    parser.add_argument(
        "--kernels", action="store_true",
        help="emit the per-fused-kernel HBM attribution of the headline "
        "raft step (bytes + estimated time share per kernel) instead of "
        "the deep dive",
    )
    args = parser.parse_args()
    if args.occupancy:
        print(json.dumps(refill_occupancy()), flush=True)
        return
    if args.kernels:
        sims = workload_sims(args.lanes)
        sim, lanes, _steps = sims["raft"]
        print(
            json.dumps({"kernel_rows": workload_kernel_rows(sim, lanes)}),
            flush=True,
        )
        return
    if args.per_workload:
        print(json.dumps(per_workload_roofline(args.lanes, args.scan)),
              flush=True)
        return
    print(
        json.dumps(
            roofline(args.lanes, args.scan, variants=not args.no_variants)
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
