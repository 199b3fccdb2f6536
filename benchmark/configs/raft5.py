"""raft5: the 5-member Raft deployment (raft5.json), its planted bugs and
its plain reference.

`build` turns the JSON sizes (and a traffic mix's overrides) into the
program's `BatchWorkload`. The reference below reads only the per-lane
arrays the timed path left in a lane's final state; it shares no code
with the program: the log window's layout is read from the state's
fields, and every guarantee is recomputed entry by entry in numpy.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# RaftState.role: follower 0, candidate 1, leader 2 (tpu/raft.py)
LEADER = 2

# the final-state fields the reference reads, per sampled lane
NODE_FIELDS = ("term", "role", "base", "head", "base_hash", "base_term",
               "log_term", "log_cmd", "log_chain", "log_len", "commit")
LANE_FIELDS = ("key0", "done", "deadlocked", "violated", "violation_step",
               "steps", "overflow", "alive_p", "clock", "epoch")
REBASE_US = 1 << 28  # a lane's clock is epoch * REBASE_US + clock (us)
# the guarantees a triage bundle's violation must break in the reference
SAFETY = ("election_safety", "log_matching", "committed_entry_lost",
          "entry_rewritten")


def _restamp(spec, fused: bool = False):
    """The deposed-leader re-stamp bug (docs/bugs_found.md #1), copied
    from benches/ttfb.py::restamp_workload: a leader that loses its role
    re-stamps its log tail with the newly adopted term. `fused` plants it
    in the fused event handler instead, which keeps the pool layout of a
    fused spec (the control runs raft5's own pool budget)."""
    import jax.numpy as jnp

    from madsim_tpu.tpu.spec import replace_handlers, wraps_event

    handler = spec.on_event if fused else spec.on_message

    def buggy(s, nid, src, kind, payload, now, key):
        state, out, timer = handler(s, nid, src, kind, payload, now, key)
        deposed = (s.role == LEADER) & (state.role != LEADER)
        log_idx = jnp.arange(s.log_term.shape[0], dtype=jnp.int32)
        in_log = log_idx < state.log_len
        log_term = jnp.where(deposed & in_log, state.term, state.log_term)
        return state._replace(log_term=log_term), out, timer

    if fused:
        # the two-handler faces must visibly derive from the new body
        on_timer = wraps_event(buggy)(
            lambda s, nid, now, key: spec.on_timer(s, nid, now, key))
        return dataclasses.replace(
            spec, on_event=buggy, on_message=buggy, on_timer=on_timer)
    return replace_handlers(spec, on_message=buggy)


def _unchecked(spec):
    """The device's invariant check switched off: every step reads clean."""
    import jax.numpy as jnp

    return dataclasses.replace(
        spec, check_invariants=lambda ns, alive, now: jnp.bool_(True))


# named plants a traffic mix may ask for (the triage cell's planted bug)
PLANTS = {"restamp_deposed_leader": _restamp}

# the controls (PERF.md, correctness): in the sweeps the re-stamp bug
# breaks log matching with the device's own check off, so only the
# reference can see it; in triage the check is off, so no bug is found.
# `control` may also name a fault of benchmark/lib/faults.py
CONTROLS = {
    "restamp_unchecked": lambda spec: _unchecked(_restamp(spec, fused=True)),
    "unchecked": _unchecked,
}


def _fault_plan(clauses):
    from madsim_tpu import nemesis

    return nemesis.FaultPlan(name="bench", clauses=tuple(
        getattr(nemesis, c["clause"])(
            **{k: v for k, v in c.items() if k != "clause"})
        for c in clauses
    ))


def build(cfg: dict, traffic: dict, control: str | None = None):
    """The program's BatchWorkload for this deployment under `traffic`.

    A traffic mix may update spec sizes (`spec`), replace the fault and
    network settings (`sim`, with an optional `fault_plan`), and name a
    planted bug (`plant`)."""
    from madsim_tpu.tpu import SimConfig, make_raft_spec
    from madsim_tpu.tpu import nemesis as tn
    from madsim_tpu.tpu.batch import BatchWorkload

    from benchmark.lib.faults import FAULTS

    spec = make_raft_spec(**{**cfg["spec"], **traffic.get("spec", {})})
    sim = SimConfig(**traffic.get("sim", cfg["sim"]))
    if traffic.get("fault_plan"):
        sim = tn.compile_plan(_fault_plan(traffic["fault_plan"]), sim)
    if traffic.get("plant"):
        spec = PLANTS[traffic["plant"]](spec)
    if control in FAULTS:
        spec, sim = FAULTS[control](spec, sim)
    elif control:
        spec = CONTROLS[control](spec)
    return BatchWorkload(spec=spec, config=sim, max_steps=cfg["max_steps"])


def sample(state, lanes) -> dict:
    """The sampled lanes' final arrays, on the host (one gather a field)."""
    import jax.numpy as jnp

    idx = jnp.asarray(np.asarray(lanes, np.int32))
    out = {f: np.asarray(getattr(state.node, f)[idx]).astype(np.int64)
           for f in NODE_FIELDS}
    out.update({f: np.asarray(getattr(state, f)[idx]).astype(np.int64)
                for f in LANE_FIELDS})
    return out


def _entries(s: dict, lane: int, node: int) -> dict:
    """Absolute index -> (term, prefix hash, hash as written) of one node's
    log: the retained window, and the compacted prefix [0, base) as the
    entry base - 1 (its term `base_term`, its hash `base_hash`). The prefix
    hash of index i folds every (term, cmd) up to i into `base_hash`,
    recomputed here from the window's raw entries; the hash as written is
    the node's own `log_chain` slot, folded when the entry was appended."""
    from benchmark.lib.seeds import fold

    LOG = s["log_term"].shape[-1]
    base, head = int(s["base"][lane, node]), int(s["head"][lane, node])
    h = np.uint32(s["base_hash"][lane, node] & 0xFFFFFFFF)
    out = {base - 1: (int(s["base_term"][lane, node]), int(h), int(h))} \
        if base > 0 else {}
    for i in range(base, int(s["log_len"][lane, node])):
        slot = (i - base + head) % LOG
        term = int(s["log_term"][lane, node, slot])
        h = fold(fold(h, term), int(s["log_cmd"][lane, node, slot]))
        out[i] = (term, int(h), int(s["log_chain"][lane, node, slot]))
    return out


def horizon_us(cfg: dict, traffic: dict) -> int:
    """The virtual time every lane of a sweep must reach."""
    return int({**cfg["sim"], **traffic.get("sim", {})}["horizon_us"])


def progress(s: dict):
    """Per sampled lane, the entries committed: the highest commit index
    over the nodes, plus one (compacted entries count)."""
    return s["commit"].max(axis=1) + 1


def reference(s: dict, seeds, horizon: int | None = None) -> list:
    """Per sampled lane, the guarantees its final state breaks (empty when
    sound). `seeds` are the lanes' seeds, in the same order. With
    `horizon`, the lane must also have run to it (a sweep's lane); without
    it, the state is a triage bundle's, stopped at its violation."""
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
        term, role, commit = s["term"][lane], s["role"][lane], s["commit"][lane]
        n_nodes = term.shape[0]
        leaders = [n for n in range(n_nodes) if role[n] == LEADER]
        if len({int(term[n]) for n in leaders}) < len(leaders):
            broken.append("election_safety")
        logs = [_entries(s, lane, n) for n in range(n_nodes)]
        # an entry keeps the term and command it was written with (Raft
        # sec. 5.3: entries are only appended or truncated, never edited)
        if any(h != written for log in logs for _t, h, written in log.values()):
            broken.append("entry_rewritten")
        matching = committed = True
        for a in range(n_nodes):
            for b in range(a + 1, n_nodes):
                top = min(commit[a], commit[b])
                for i in set(logs[a]) & set(logs[b]):
                    (ta, ha, _), (tb, hb, _) = logs[a][i], logs[b][i]
                    if ta == tb and ha != hb:
                        matching = False  # same index and term, other prefix
                    if i <= top and ha != hb:
                        committed = False  # committed prefixes differ
        alive = int(s["alive_p"][lane].reshape(-1)[0])
        for ld in leaders:
            if not alive >> ld & 1:
                continue  # a crashed leader's log is frozen, not bound
            for a in range(n_nodes):
                c = int(commit[a])
                if term[a] > term[ld] or c < 0:
                    continue
                if s["log_len"][lane, ld] - 1 < c:
                    committed = False  # the leader lacks a committed entry
                elif c in logs[ld] and c in logs[a] and \
                        logs[ld][c][1] != logs[a][c][1]:
                    committed = False
        if not matching:
            broken.append("log_matching")
        if not committed:
            broken.append("committed_entry_lost")
        out.append(broken)
    return out
