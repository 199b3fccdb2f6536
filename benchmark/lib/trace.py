"""The profiler trace reduced to device busy/idle time, op totals and the
collective share.

`load` reads the `.xplane.pb` that `jax.profiler` writes into a plain
dict (`events`), the same schema as the committed test excerpt:

    {"devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
                         "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

`reduce` turns that, clipped to a window, into a `Summary`. Busy time is
the union of the intervals in which an XLA program or op ran on a device,
so nested or overlapping events count once. Programs are counted as well
as ops because the profiler drops op events past a few million per
device (a triage cycle's 100,000-step traced replays reach that), while
every program event arrives.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|all_reduce|all_gather|reduce_scatter|collective_permute|all_to_all")

CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """Device op and module events and host events of one xplane file."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: dict = {"devices": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = out["devices"].setdefault(plane.name,
                                            {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key].extend([short_name(e.name), e.start_ns,
                                     e.duration_ns] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns, e.duration_ns] for e in line.events
                    if e.duration_ns > 0 or e.name.startswith("bench."))
    return out


def short_name(name: str) -> str:
    """An op's HLO instruction name without its text ("%fusion.3 = ..."
    -> "fusion.3")."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Sorted disjoint union of `intervals`, clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of a sorted disjoint union within [lo, hi]."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def _totals(events, lo: float, hi: float) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out[name] = out.get(name, 0.0) + (b - a)
    return out


@dataclasses.dataclass
class Device:
    busy: List[Interval]  # sorted disjoint busy intervals
    busy_ns: float
    op_ns: Dict[str, float]  # by op name, clipped to the window
    collective_ns: float  # union of collective-op intervals
    idle: List[Interval]  # the gaps between busy intervals
    module_ns: Dict[str, float]  # by program name, clipped to the window


@dataclasses.dataclass
class Summary:
    window_ns: float
    devices: Dict[str, Device]

    def idle_share(self) -> float:
        """Idle share of the most idle device."""
        return max(1.0 - d.busy_ns / self.window_ns
                   for d in self.devices.values())

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(d.busy_ns for d in self.devices.values()) / (
            1e9 * len(self.devices))

    def collective_share(self) -> Optional[float]:
        """Collective share of busy time on the busiest device."""
        d = max(self.devices.values(), key=lambda d: d.busy_ns)
        return d.collective_ns / d.busy_ns if d.collective_ns else None

    def program_ns(self, jit_name: str) -> float:
        """Device time of the programs jitted from function `jit_name`
        (their events read "jit_<name>(<fingerprint>)"), summed over the
        devices."""
        prefix = f"jit_{jit_name}("
        return sum(ns for d in self.devices.values()
                   for n, ns in d.module_ns.items() if n.startswith(prefix))

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """The ops that took most device time, seconds per device. Control
        flow ops (while, conditional, call) are left out: their events
        span the ops they run."""
        tot: Dict[str, float] = {}
        for d in self.devices.values():
            for n, ns in d.op_ns.items():
                if not CONTAINER.match(n):
                    tot[n] = tot.get(n, 0.0) + ns / (1e9 * len(self.devices))
        return sorted(tot.items(), key=lambda kv: -kv[1])[:k]


def reduce(events: dict, lo: float, hi: float) -> Summary:
    """Reduce `events` (see `load`) over the window [lo, hi] (ns)."""
    devices = {}
    for name, dev in sorted(events["devices"].items()):
        busy = union([(s, s + d) for _, s, d in dev["ops"] + dev["modules"]],
                     lo, hi)
        coll = union([(s, s + d) for n, s, d in dev["ops"]
                      if COLLECTIVE.search(n)], lo, hi)
        devices[name] = Device(
            busy=busy, busy_ns=_length(busy), op_ns=_totals(dev["ops"], lo, hi),
            collective_ns=_length(coll), idle=gaps(busy, lo, hi),
            module_ns=_totals(dev["modules"], lo, hi))
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    return Summary(window_ns=hi - lo, devices=devices)


def label_idle(summary: Summary, host: Sequence[Tuple[str, float, float]],
               k: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds by what the host was doing: each gap of the most idle
    device goes to the innermost host span around its midpoint. Returns
    the `k` labels with most idle time."""
    dev = min(summary.devices.values(), key=lambda d: d.busy_ns)
    by: Dict[str, float] = {}
    for a, b in dev.idle:
        mid = (a + b) / 2
        around = [(d, n) for n, s, d in host if s <= mid <= s + d]
        label = min(around)[1] if around else "no host span"
        by[label] = by.get(label, 0.0) + (b - a) / 1e9
    return sorted(by.items(), key=lambda kv: -kv[1])[:k]


def window_of(events: dict, start: str, end: str) -> Interval:
    """The traced stretch: from the host marker `start` to the marker
    `end`."""
    marks = {n: s for n, s, _ in events["host"] if n in (start, end)}
    if len(marks) < 2:
        raise ValueError(f"no host markers {start!r} and {end!r} in the "
                         "trace")
    return marks[start], marks[end]
