"""Per-lane violation traces: the device-side repro microscope.

The reference prints the failing seed so the developer can replay the exact
trajectory under a debugger (runtime/mod.rs:194-199). The batched engine's
analog: re-run a violating seed single-lane through the SAME jitted step
function with event capture on (`BatchedSim.run_traced`), then render the
captured TraceRecord stream as a readable event log — every message
delivery (src→dst, kind, payload), timer fire, crash/restart and partition
split/heal, stamped with step index and virtual time, ending at the exact
step the invariant broke. No host twin needed: the trace IS the trajectory
that violated, bit-identical to the lane inside the original batch. The
device stops stepping one step after the lane is done; the record keeps
all `max_steps` rows, those past the stop repeating the done lane's.

    state, recs = sim.run_traced(bad_seed)
    events = extract_trace(recs, kind_names=["REQUEST_VOTE", ...])
    print(format_trace(events[-200:]))     # the tail leading to the bug
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from .. import telemetry
from .engine import BatchedSim, TraceRecord


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    step: int
    t_us: int
    # deliver | timer | crash | restart | split | heal | clog | unclog |
    # spike_on | spike_off | remove | join | disk_slow | disk_crash |
    # disk_recover | violation | deadlock
    kind: str
    node: int = -1  # acting node (dst for deliver; src for clog)
    src: int = -1  # sender (deliver only)
    msg_kind: int = -1  # protocol message kind (deliver only)
    msg_name: str = ""  # human name for msg_kind, if provided
    payload: Optional[tuple] = None
    detail: str = ""
    # causal lineage (BatchedSim(lineage=True) traces only; -1 otherwise):
    # this event's global id, the delivered message's send-event id, and
    # the acting node's post-event Lamport clock — see madsim_tpu/causal.py
    eid: int = -1
    sent_eid: int = -1  # deliver events only
    lam: int = -1

    def __str__(self) -> str:
        t = self.t_us / 1e6
        if self.kind == "deliver":
            name = self.msg_name or str(self.msg_kind)
            return (
                f"[{t:9.6f}s #{self.step}] node{self.node} <- node{self.src} "
                f"{name} {list(self.payload or ())}"
            )
        if self.kind == "timer":
            return f"[{t:9.6f}s #{self.step}] node{self.node} timer fired"
        if self.kind in ("crash", "restart"):
            return f"[{t:9.6f}s #{self.step}] {self.kind} node{self.node}"
        if self.kind == "split":
            return f"[{t:9.6f}s #{self.step}] partition split {self.detail}"
        if self.kind == "heal":
            return f"[{t:9.6f}s #{self.step}] partition healed"
        if self.kind in ("clog", "unclog"):
            return f"[{t:9.6f}s #{self.step}] {self.kind} link {self.detail}"
        if self.kind == "spike_on":
            return f"[{t:9.6f}s #{self.step}] latency spike begins {self.detail}"
        if self.kind == "spike_off":
            return f"[{t:9.6f}s #{self.step}] latency spike ends"
        if self.kind == "remove":
            return (
                f"[{t:9.6f}s #{self.step}] node{self.node} REMOVED from "
                "membership"
            )
        if self.kind == "join":
            return (
                f"[{t:9.6f}s #{self.step}] node{self.node} joins as a "
                "fresh replica"
            )
        if self.kind == "disk_slow":
            return (
                f"[{t:9.6f}s #{self.step}] node{self.node} disk degrades "
                "(slow writes, failing fsync)"
            )
        if self.kind == "disk_crash":
            w = " (torn tail)" if self.detail else ""
            return (
                f"[{t:9.6f}s #{self.step}] node{self.node} disk dies{w} "
                "— unsynced state lost"
            )
        if self.kind == "disk_recover":
            return (
                f"[{t:9.6f}s #{self.step}] node{self.node} recovers from "
                "its durable watermark"
            )
        return f"[{t:9.6f}s #{self.step}] {self.kind.upper()} {self.detail}"


def extract_trace(
    recs: TraceRecord,
    kind_names: Optional[Sequence[str]] = None,
    lane: int = 0,
) -> List[TraceEvent]:
    """Flatten a [T, L, ...] TraceRecord into a chronological event list.

    Steps after the lane finished record no events (active lanes only), so
    the list self-truncates at the violation/horizon.
    """
    return _extract(recs, kind_names, lane)[0]


def _extract(
    recs: TraceRecord, kind_names: Optional[Sequence[str]], lane: int,
) -> Tuple[List[TraceEvent], int]:
    """extract_trace's events, and how many steps with activity it
    visited."""
    # times are (epoch, offset) pairs — combine to absolute int64 us
    # (spec.REBASE_US; the record's offsets are post-rebase, so a step that
    # rebased reports its events in the NEW basis consistently)
    from .spec import REBASE_US

    epoch = np.asarray(recs.epoch, np.int64)[:, lane]  # [T]
    clock = np.asarray(recs.clock, np.int64)[:, lane] + epoch * REBASE_US
    t_evt = (
        np.asarray(recs.t_evt, np.int64)[:, lane] + epoch[:, None] * REBASE_US
    )  # [T,N] per-node event times
    msg_fired = np.asarray(recs.msg_fired)[:, lane]  # [T,N]
    msg_src = np.asarray(recs.msg_src)[:, lane]
    msg_kind = np.asarray(recs.msg_kind)[:, lane]
    msg_payload = np.asarray(recs.msg_payload)[:, lane]  # [T,N,P]
    timer_fired = np.asarray(recs.timer_fired)[:, lane]
    crash = np.asarray(recs.crash)[:, lane]
    restart = np.asarray(recs.restart)[:, lane]
    split = np.asarray(recs.split)[:, lane]
    heal = np.asarray(recs.heal)[:, lane]
    side_mask = np.asarray(recs.side_mask)[:, lane]
    violation = np.asarray(recs.violation)[:, lane]
    deadlock = np.asarray(recs.deadlock)[:, lane]
    clog_src = np.asarray(recs.clog_src)[:, lane]
    clog_dst = np.asarray(recs.clog_dst)[:, lane]
    unclog = np.asarray(recs.unclog)[:, lane]
    spike_on = np.asarray(recs.spike_on)[:, lane]
    spike_off = np.asarray(recs.spike_off)[:, lane]
    remove = np.asarray(recs.remove)[:, lane]
    join = np.asarray(recs.join)[:, lane]
    disk_slow = np.asarray(recs.disk_slow)[:, lane]
    disk_crash = np.asarray(recs.disk_crash)[:, lane]
    disk_recover = np.asarray(recs.disk_recover)[:, lane]
    disk_torn = np.asarray(recs.disk_torn)[:, lane]
    # lineage plane (BatchedSim(lineage=True) traces only)
    has_lin = recs.evt_eid is not None
    if has_lin:
        evt_eid = np.asarray(recs.evt_eid, np.int64)[:, lane]  # [T,N]
        sent_eid = np.asarray(recs.sent_eid, np.int64)[:, lane]
        lam = np.asarray(recs.lam, np.int64)[:, lane]
        EID_NONE = 0xFFFFFFFF

    T, N = msg_fired.shape
    events: List[TraceEvent] = []
    # steps with any activity (cheap pre-filter: most post-done steps are empty)
    busy = (
        msg_fired.any(1) | timer_fired.any(1) | (crash >= 0) | (restart >= 0)
        | split | heal | violation | deadlock
        | (clog_src >= 0) | unclog | spike_on | spike_off
        | (remove >= 0) | (join >= 0)
        | (disk_slow >= 0) | (disk_crash >= 0) | (disk_recover >= 0)
    )
    busy_steps = np.nonzero(busy)[0]
    for t in busy_steps:
        t = int(t)
        # chaos fires at the window start t_next == min(t_evt) (inactive
        # nodes default to it); violation/deadlock are end-of-step facts and
        # keep the lane clock (the latest event time processed)
        t_chaos = int(t_evt[t].min())
        t_us = int(clock[t])
        # node events carry their own virtual times (the lookahead window
        # batches causally independent events into one step); render them
        # in time order within the step
        node_events: List[TraceEvent] = []
        for n in range(N):
            if msg_fired[t, n]:
                mk = int(msg_kind[t, n])
                node_events.append(
                    TraceEvent(
                        step=t, t_us=int(t_evt[t, n]), kind="deliver", node=n,
                        src=int(msg_src[t, n]), msg_kind=mk,
                        msg_name=(
                            kind_names[mk]
                            if kind_names and 0 <= mk < len(kind_names)
                            else ""
                        ),
                        payload=tuple(int(x) for x in msg_payload[t, n]),
                        eid=(
                            int(evt_eid[t, n])
                            if has_lin and evt_eid[t, n] != EID_NONE else -1
                        ),
                        sent_eid=(
                            int(sent_eid[t, n])
                            if has_lin and sent_eid[t, n] != EID_NONE else -1
                        ),
                        lam=int(lam[t, n]) if has_lin else -1,
                    )
                )
            if timer_fired[t, n]:
                node_events.append(
                    TraceEvent(
                        step=t, t_us=int(t_evt[t, n]), kind="timer", node=n,
                        eid=(
                            int(evt_eid[t, n])
                            if has_lin and evt_eid[t, n] != EID_NONE else -1
                        ),
                        lam=int(lam[t, n]) if has_lin else -1,
                    )
                )
        node_events.sort(key=lambda e: e.t_us)
        events.extend(node_events)
        if crash[t] >= 0:
            events.append(
                TraceEvent(step=t, t_us=t_chaos, kind="crash", node=int(crash[t]))
            )
        if restart[t] >= 0:
            events.append(
                TraceEvent(step=t, t_us=t_chaos, kind="restart", node=int(restart[t]))
            )
        if split[t]:
            sides = int(side_mask[t])
            a = [n for n in range(N) if sides >> n & 1]
            b = [n for n in range(N) if not sides >> n & 1]
            events.append(
                TraceEvent(step=t, t_us=t_chaos, kind="split", detail=f"{a} | {b}")
            )
        if heal[t]:
            events.append(TraceEvent(step=t, t_us=t_chaos, kind="heal"))
        if clog_src[t] >= 0:
            events.append(
                TraceEvent(
                    step=t, t_us=t_chaos, kind="clog", node=int(clog_src[t]),
                    src=int(clog_dst[t]),
                    detail=f"{int(clog_src[t])}->{int(clog_dst[t])}",
                )
            )
        if unclog[t]:
            events.append(TraceEvent(step=t, t_us=t_chaos, kind="unclog"))
        if spike_on[t]:
            events.append(TraceEvent(step=t, t_us=t_chaos, kind="spike_on"))
        if spike_off[t]:
            events.append(TraceEvent(step=t, t_us=t_chaos, kind="spike_off"))
        if remove[t] >= 0:
            events.append(
                TraceEvent(
                    step=t, t_us=t_chaos, kind="remove", node=int(remove[t])
                )
            )
        if join[t] >= 0:
            events.append(
                TraceEvent(
                    step=t, t_us=t_chaos, kind="join", node=int(join[t])
                )
            )
        if disk_slow[t] >= 0:
            events.append(
                TraceEvent(
                    step=t, t_us=t_chaos, kind="disk_slow",
                    node=int(disk_slow[t]),
                )
            )
        if disk_crash[t] >= 0:
            events.append(
                TraceEvent(
                    step=t, t_us=t_chaos, kind="disk_crash",
                    node=int(disk_crash[t]),
                    detail="torn" if disk_torn[t] else "",
                )
            )
        if disk_recover[t] >= 0:
            events.append(
                TraceEvent(
                    step=t, t_us=t_chaos, kind="disk_recover",
                    node=int(disk_recover[t]),
                    detail="torn" if disk_torn[t] else "",
                )
            )
        if violation[t]:
            events.append(
                TraceEvent(
                    step=t, t_us=t_us, kind="violation",
                    detail="invariant check failed",
                )
            )
        if deadlock[t]:
            events.append(
                TraceEvent(step=t, t_us=t_us, kind="deadlock", detail="no runnable events")
            )
    # a node's deferred event can be processed a step after another node's
    # later-time in-window event; a stable time sort restores the
    # chronological contract (per-node and same-instant orders preserved)
    events.sort(key=lambda e: e.t_us)
    return events, int(busy_steps.size)


def format_trace(events: Sequence[TraceEvent]) -> str:
    return "\n".join(str(e) for e in events)


def trace_seed(
    sim: BatchedSim,
    seed: int,
    max_steps: int = 20_000,
    kind_names: Optional[Sequence[str]] = None,
    ctl=None,
) -> List[TraceEvent]:
    """One-call microscope: re-run `seed` traced and return its event list.

    `ctl` (a single-lane TriageCtl; triage-mode sims only) traces a shrunk
    candidate — suppressed clauses/occurrences never appear in the events.

    Three spans split the call: `scan[trace]` (the traced scan, to its
    last record; labelled with the `steps` the lane ran before it was done
    and the `max_steps` of its record, since the scan stops with the
    lane), `fetch[trace]` (one transfer of the whole record to the host;
    labelled with its `bytes`) and `extract[trace]` (the host decode;
    labelled with the active `steps` it visited and the `events` it
    returned).
    """
    with telemetry.span("scan", site="trace") as sp:
        state, recs = sim.run_traced(seed, max_steps=max_steps, ctl=ctl)
        jax.block_until_ready(recs)
        # a live step is active (counted in `steps`) or finds nothing to
        # run (`deadlocked`, which ends the lane)
        steps, deadlocked = jax.device_get(
            (state.steps[0], state.deadlocked[0])
        )
        sp.set(steps=int(steps) + int(deadlocked), max_steps=max_steps)
    with telemetry.span("fetch", site="trace") as sp:
        recs = jax.device_get(recs)
        sp.set(bytes=sum(x.nbytes for x in jax.tree_util.tree_leaves(recs)))
    with telemetry.span("extract", site="trace") as sp:
        events, steps = _extract(recs, kind_names, lane=0)
        sp.set(steps=steps, events=len(events))
    return events
