"""Device us per engine-loop iteration of the `_run` ops under a step
phase's `membership` scope (`step/chaos/membership`: the Reconfig
clause's remove and join, with the fresh replica's init;
`step/network/membership`: the member filter on sends), over the
iterations `engine_step_us` counts. Those ops stay in their phase's
`step_<phase>_us` too. Only a program with the Reconfig clause on has
them; a program without the scope (the parent of this metric) reads None.

The flow is `scopes.phases_ns`'s with another instruction map, which that
reader does not take: so the profile is loaded once more here."""

import re

from benchmark.lib import scopes
from benchmark.lib import trace as tracelib

MEMBERSHIP = re.compile(r"/step/(" + "|".join(scopes.PHASES)
                        + r")/membership(?:/|\")")


def membership_ops(hlo_text: str) -> dict:
    """Instruction name -> its step phase where its metadata puts it under
    that phase's membership scope, else None (a fused op by its root)."""
    out = {}
    for line in hlo_text.splitlines():
        m = scopes.INSTRUCTION.match(line)
        if m:
            scoped = MEMBERSHIP.search(line)
            out[m.group(1)] = scoped.group(1) if scoped else None
    return out


def read(run):
    path = scopes.xplane_path(run) if run.trace is not None else None
    if path is None:
        return None
    events = tracelib.load(path)
    traced = run.records[:int(run.cell.traffic.get("trace_calls", 1))]
    steps = sum(r.get("loop_steps", 0) for r in traced)
    if len(scopes.run_programs(events)) != 1 or not steps:
        return None
    lo, hi = tracelib.window_of(events, "bench.traced.start",
                                "bench.traced.end")
    by = scopes.phase_ns(events, lo, hi,
                         membership_ops(scopes.run_program_text(run)))
    if by is None:
        return None
    return sum(ns for p, ns in by.items() if p != scopes.UNSCOPED) / steps / 1e3
