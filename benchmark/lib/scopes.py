"""What the program says about itself: its span tree (telemetry spans)
and the named phases of its engine step in the device trace.

Span readers work on `run.spans`, the program's `telemetry` records of
the window. Each span is `name` plus a `site` label; spans carry
`span_id` and `parent_id` where the program records the tree, and a
program without them (or without a span) reads None.

Phase readers work on the profiled stretch. Every op of the engine step
carries the named scope `step/<phase>` in its metadata; `phase_ns`
adds up, over the jitted `_run` programs in the window, the device time
of each phase's ops and of the ops under no phase. The profile names an
op by its HLO instruction and keeps no metadata, so the map from
instruction to phase comes from the compiled `_run` program's HLO text.
A fused op belongs to the phase of its root instruction, whose metadata
the fusion carries. The map is built once per trace file and shared by
the readers (a module-level cache keyed by the file's path).
"""

from __future__ import annotations

import bisect
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace as tracelib

TRACED_PROGRAM = "_run"  # BatchedSim._run, tpu/engine.py
PHASES = ("select", "handlers", "chaos", "network", "invariants", "finish")
UNSCOPED = "unscoped"
PHASE_OF = re.compile(r"/step/(" + "|".join(PHASES) + r")(?:/|\")")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")


# -------------------------------------------------------------------- spans


def label(span) -> str:
    site = span.labels.get("site", "")
    return f"{span.name}[{site}]" if site else span.name


def named(spans: Sequence, want: str) -> list:
    """The spans labelled `want` (`name[site]`)."""
    return [s for s in spans if label(s) == want]


def per_bundle_ms(run, want: str) -> Optional[float]:
    """Milliseconds of the spans labelled `want` per bundle written."""
    bundles = sum(1 for r in run.records if r.get("bundle"))
    spans = named(run.spans, want)
    return 1e3 * sum(s.dur_s for s in spans) / bundles \
        if bundles and spans else None


def mean_ms(run, want: str) -> Optional[float]:
    spans = named(run.spans, want)
    return 1e3 * sum(s.dur_s for s in spans) / len(spans) if spans else None


def descendants(root, spans: Sequence) -> list:
    """The spans under `root` in the tree (children, theirs, ...)."""
    kids: Dict[int, list] = {}
    for s in spans:
        kids.setdefault(getattr(s, "parent_id", None), []).append(s)
    out, todo = [], [getattr(root, "span_id", None)]
    while todo:
        for s in kids.get(todo.pop(), []):
            out.append(s)
            todo.append(s.span_id)
    return out


def covered_s(root, parts: Sequence) -> float:
    """How much of `root`'s interval the union of `parts` covers."""
    lo, hi = root.t0_s, root.t0_s + root.dur_s
    total, end = 0.0, lo
    for a, b in sorted((max(p.t0_s, lo), min(p.t0_s + p.dur_s, hi))
                       for p in parts):
        a = max(a, end)
        if b > a:
            total += b - a
            end = b
    return total


def host_self_ms(run, root_label: str, waits: Tuple[str, ...]) -> Optional[float]:
    """Mean over the calls (spans labelled `root_label`) of the call's
    time minus the part its descendants named in `waits` cover: the
    host's own work, without waiting on the device or the oracle."""
    roots = [r for r in named(run.spans, root_label)
             if getattr(r, "span_id", None) is not None]
    if not roots:
        return None
    own = [r.dur_s - covered_s(r, [d for d in descendants(r, run.spans)
                                   if d.name in waits])
           for r in roots]
    return 1e3 * sum(own) / len(own)


# ------------------------------------------------------------------- phases


def phase_of(text: str) -> Optional[str]:
    """The step phase named in an instruction's metadata, if any."""
    m = PHASE_OF.search(text)
    return m.group(1) if m else None


def hlo_phases(hlo_text: str) -> Dict[str, Optional[str]]:
    """Instruction name -> step phase (None where its metadata names
    none), from a compiled program's HLO text. A fusion carries the
    metadata of its root instruction, so a fused op takes the phase of
    its root."""
    out: Dict[str, Optional[str]] = {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = phase_of(line)
    return out


def run_program_text(run) -> str:
    """The compiled `_run` program of the cell's chunks, as HLO text: the
    profile names each op by its instruction and keeps no metadata, so the
    phases come from the program itself, lowered at the chunk's shape and
    segment length (run_batch's defaults, as the window ran them) and
    compiled through the compile cache."""
    import jax
    import jax.numpy as jnp

    from madsim_tpu.tpu import BatchedSim
    from madsim_tpu.tpu.batch import DEFAULT_CHUNK
    from madsim_tpu.tpu.engine import DEFAULT_DISPATCH_STEPS

    cell = run.cell
    wl = cell.factory.build(cell.config, cell.traffic, None)
    sim = BatchedSim(wl.spec, wl.config)
    lanes = min(DEFAULT_CHUNK, int(cell.traffic["seeds_per_call"]))
    state = jax.eval_shape(sim.init,
                           jax.ShapeDtypeStruct((lanes,), jnp.uint32))
    steps = min(DEFAULT_DISPATCH_STEPS, int(wl.max_steps))
    return sim._run.lower(sim, state, steps).compile().as_text()


def run_programs(events: dict, program: str = TRACED_PROGRAM) -> set:
    """The distinct `program` programs in a trace (by their event name,
    which carries the executable's fingerprint)."""
    prefix = f"jit_{program}("
    return {n for dev in events["devices"].values()
            for n, _, _ in dev["modules"] if n.startswith(prefix)}


def phase_ns(events: dict, lo: float, hi: float,
             phases: Dict[str, Optional[str]],
             program: str = TRACED_PROGRAM) -> Optional[Dict[str, float]]:
    """Device ns of the ops inside the `program` programs within [lo, hi]
    (`events` as `trace.load` gives them), by phase (`phases`: instruction
    name -> phase), summed over the devices; ops under no phase are
    `UNSCOPED`. Control-flow ops (while, conditional, call) are left out,
    since their events span the ops they run. None where no instruction
    names a phase."""
    if not any(phases.values()):
        return None
    prefix = f"jit_{program}("
    out = {p: 0.0 for p in PHASES + (UNSCOPED,)}
    for dev in events["devices"].values():
        progs = tracelib.union(
            [(s, s + d) for n, s, d in dev["modules"] if n.startswith(prefix)],
            lo, hi)
        starts = [a for a, _ in progs]
        for name, s, d in dev["ops"]:
            if progs and not tracelib.CONTAINER.match(name):
                ns = _inside(progs, starts, s, s + d)
                if ns > 0:
                    out[phases.get(name) or UNSCOPED] += ns
    return out


def _inside(progs: List[Tuple[float, float]], starts: List[float],
            a: float, b: float) -> float:
    """Length of [a, b] inside the sorted disjoint intervals `progs`."""
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    total = 0.0
    while i < len(progs) and progs[i][0] < b:
        x, y = max(progs[i][0], a), min(progs[i][1], b)
        if y > x:
            total += y - x
        i += 1
    return total


_CACHE: Dict[str, Optional[Dict[str, float]]] = {}


def xplane_path(run) -> Optional[str]:
    """The profile the harness wrote for this run's cell, if any."""
    from . import harness

    try:
        return tracelib.find_xplane(os.path.join(harness.OUT_DIR,
                                                 run.cell.name, "trace"))
    except FileNotFoundError:
        return None


def phases_ns(run) -> Optional[Dict[str, float]]:
    """`phase_ns` over the run's profiled stretch, once per trace file.
    None without a profile, or where the stretch holds no `_run` program
    or more than one (one map of instruction names fits one program)."""
    if run.trace is None:
        return None
    path = xplane_path(run)
    if path is None:
        return None
    if path not in _CACHE:
        events = tracelib.load(path)
        lo, hi = tracelib.window_of(events, "bench.traced.start",
                                    "bench.traced.end")
        _CACHE[path] = (
            phase_ns(events, lo, hi, hlo_phases(run_program_text(run)))
            if len(run_programs(events)) == 1 else None)
    return _CACHE[path]


def step_us(run, phase: str) -> Optional[float]:
    """Device us of `phase` per iteration of the engine loop, over the same
    iterations as `engine_step_us`: the traced calls' chunks' longest
    lanes."""
    by = phases_ns(run)
    traced = run.records[:int(run.cell.traffic.get("trace_calls", 1))]
    steps = sum(r.get("loop_steps", 0) for r in traced)
    if by is None or not steps:
        return None
    return by[phase] / steps / 1e3
