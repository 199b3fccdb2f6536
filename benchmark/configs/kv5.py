"""kv5: the 5-replica linearizable KV deployment (kv5.json), its control
and its plain reference.

The reference is a plain Wing-Gong search per key over the acked
histories that the timed sweep recorded in its final state. It is
written here from the definition (Herlihy & Wing 1990; Wing & Gong
1993) and shares no code with the program's own checker
(tpu/linearize.py), so a later change there cannot move it.
"""

from __future__ import annotations

import sys

import numpy as np

OP_READ, OP_WRITE = 1, 2  # the history ring's op kinds (tpu/kv.py)
HISTORY_FIELDS = ("h_kind", "h_key", "h_val", "h_rev", "h_tinv", "h_trsp")
LANE_FIELDS = ("key0", "done", "deadlocked", "violated", "violation_step",
               "steps", "overflow", "clock", "epoch")
REBASE_US = 1 << 28  # a lane's clock is epoch * REBASE_US + clock (us)

# the control: replicas answer reads from their local store and skip the
# quorum probe, so a deposed primary serves stale values
CONTROLS = {"stale_local_read": "buggy_local_read_spec"}


def build(cfg: dict, traffic: dict, control: str | None = None):
    """The program's BatchWorkload for this deployment (kv_workload as is;
    a control swaps in the stale-read spec)."""
    import dataclasses

    from madsim_tpu.tpu import kv

    from benchmark.lib.faults import FAULTS

    wl = kv.kv_workload(**{**cfg["workload"], **traffic.get("workload", {})})
    if control in FAULTS:
        spec, sim = FAULTS[control](wl.spec, wl.config)
        wl = dataclasses.replace(wl, spec=spec, config=sim, host_repro=None)
    elif control:
        # no host microscope for the control's violating seeds: the
        # comparison reads the sweep alone
        wl = dataclasses.replace(
            wl, spec=getattr(kv, CONTROLS[control])(base=wl.spec),
            host_repro=None)
    return dataclasses.replace(wl, max_steps=cfg["max_steps"])


def sample(state, lanes) -> dict:
    import jax.numpy as jnp

    idx = jnp.asarray(np.asarray(lanes, np.int32))
    out = {f: np.asarray(getattr(state.node, f)[idx]).astype(np.int64)
           for f in HISTORY_FIELDS}
    out.update({f: np.asarray(getattr(state, f)[idx]).astype(np.int64)
                for f in LANE_FIELDS})
    return out


def _register_linearizable(ops) -> bool:
    """Wing-Gong depth-first search for one register's history.

    `ops`: (invoke, respond, is_write, value) tuples; the register starts
    at 0. A read whose value no recorded write produced is left out: the
    ring keeps acked ops only, so its write may be unacked or evicted."""
    writes = [o for o in ops if o[2]]
    written = {o[3] for o in writes}
    if len(written) < len(writes):
        return False  # write values are unique by construction
    ops = sorted((o for o in ops if o[2] or o[3] == 0 or o[3] in written),
                 key=lambda o: (o[0], o[1]))
    n = len(ops)
    failed = set()

    def search(left: frozenset, value: int) -> bool:
        if not left:
            return True
        if (left, value) in failed:
            return False
        first_response = min(ops[i][1] for i in left)
        for i in sorted(left):
            inv, _, is_write, val = ops[i]
            if inv > first_response:
                break  # sorted by invocation: no later op can go first
            if not is_write and val != value:
                continue
            if search(left - {i}, val if is_write else value):
                return True
        failed.add((left, value))
        return False

    limit = sys.getrecursionlimit()
    sys.setrecursionlimit(max(limit, n + 100))
    try:
        return search(frozenset(range(n)), 0)
    finally:
        sys.setrecursionlimit(limit)


def horizon_us(cfg: dict, traffic: dict) -> int:
    """The virtual time every lane of a sweep must reach."""
    w = {**cfg["workload"], **traffic.get("workload", {})}
    return int(w["virtual_secs"] * 1e6)


def progress(s: dict):
    """Per sampled lane, the acked operations its history ring holds."""
    return (s["h_kind"] > 0).reshape(len(s["h_kind"]), -1).sum(axis=1)


def reference(s: dict, seeds, horizon: int | None = None) -> list:
    """Per sampled lane, the guarantees its final state breaks; with
    `horizon`, the lane must also have run to it."""
    from benchmark.lib.seeds import key_from_seed

    keys = key_from_seed(seeds)
    out = []
    for lane in range(len(seeds)):
        broken = []
        if int(s["key0"][lane]) != int(keys[lane]):
            broken.append("not_this_seed")
        if horizon is not None:
            now = int(s["epoch"][lane]) * REBASE_US + int(s["clock"][lane])
            if not s["done"][lane] or s["deadlocked"][lane] or now < horizon:
                broken.append("short_of_horizon")
        by_key: dict = {}
        kind = s["h_kind"][lane]
        for n, i in zip(*np.nonzero(kind > 0)):
            by_key.setdefault(int(s["h_key"][lane, n, i]), []).append((
                int(s["h_tinv"][lane, n, i]), int(s["h_trsp"][lane, n, i]),
                int(kind[n, i]) == OP_WRITE, int(s["h_val"][lane, n, i]),
            ))
        if not all(_register_linearizable(ops) for ops in by_key.values()):
            broken.append("linearizability")
        out.append(broken)
    return out
