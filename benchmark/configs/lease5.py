"""lease5: the etcd lease/keepalive deployment under membership churn
(lease5.json), its planted bug and its plain reference.

`build` turns the JSON sizes (and a traffic mix's overrides) into the
program's `BatchWorkload`. The reference below reads only the per-lane
arrays the timed path left in a lane's final state and shares no code
with the program: node 0 is the lease server, nodes 1.. are the client
sessions, and each guarantee of lease5.json is recomputed in numpy, with
the membership churn the configuration's Reconfig clause must leave.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

SERVER = 0  # tpu/lease.py: node 0 holds the lease head
# the final-state fields the reference reads, per sampled lane
NODE_FIELDS = ("inc", "held", "my_token", "my_expiry", "l_holder", "l_inc",
               "l_token", "l_expiry")
LANE_FIELDS = ("key0", "done", "deadlocked", "violated", "violation_step",
               "steps", "overflow", "clock", "epoch", "member_p",
               "member_epoch")
NEM_FIELDS = ("reconfig_k", "reconf_node")  # the Reconfig clause's schedule
REBASE_US = 1 << 28  # a lane's clock is epoch * REBASE_US + clock (us)

with open(os.path.splitext(__file__)[0] + ".json") as f:
    _SIM = json.load(f)["sim"]
# a remove comes at most interval_hi after the previous join (or the
# start), and its join at most down_hi after it: a lane at the horizon has
# completed at least horizon // CYCLE_US remove/join cycles
CYCLE_US = _SIM["nem_reconfig_interval_hi_us"] + _SIM["nem_reconfig_down_hi_us"]


def _unchecked(spec):
    """The device's invariant check switched off: every step reads clean."""
    import jax.numpy as jnp

    return dataclasses.replace(
        spec, check_invariants=lambda ns, alive, now: jnp.bool_(True))


def _zombie_unchecked(kw: dict, sim):
    """The zombie-lease bug (tpu/lease.py's `buggy_zombie_lease`: renewal
    matched on the holder's node id alone) with the device check off: only
    the reference can see the stale incarnation the server keeps renewing."""
    from madsim_tpu.tpu import make_lease_spec

    return _unchecked(make_lease_spec(**kw, buggy_zombie_lease=True)), sim


def _false_alarm(kw: dict, sim):
    """The device check fires on a sound state: as soon as the server has
    granted a lease (no guarantee is broken there)."""
    from madsim_tpu.tpu import make_lease_spec

    return dataclasses.replace(
        make_lease_spec(**kw),
        check_invariants=lambda ns, alive, now: ns.l_token[SERVER] < 1), sim


def _reconfig_off(kw: dict, sim):
    """The Reconfig clause switched off: no lease guarantee breaks, but no
    member ever leaves or joins."""
    from madsim_tpu.tpu import make_lease_spec

    return make_lease_spec(**kw), dataclasses.replace(
        sim, nem_reconfig_interval_hi_us=0)


# the controls (PERF.md, correctness), each from the spec's sizes and the
# SimConfig; they take the place of a fault of benchmark/lib/faults.py of
# the same name, and `control` may also name any other fault there
CONTROLS = {"zombie_unchecked": _zombie_unchecked,
            "false_alarm": _false_alarm,
            "reconfig_off": _reconfig_off}


def build(cfg: dict, traffic: dict, control: str | None = None):
    """The program's BatchWorkload for this deployment under `traffic`,
    whose `spec` and `sim` update the configuration's."""
    from madsim_tpu.tpu import SimConfig, make_lease_spec
    from madsim_tpu.tpu.batch import BatchWorkload

    from benchmark.lib.faults import FAULTS

    kw = {**cfg["spec"], **traffic.get("spec", {})}
    sim = SimConfig(**{**cfg["sim"], **traffic.get("sim", {})})
    if control in CONTROLS:
        spec, sim = CONTROLS[control](kw, sim)
    else:
        spec = make_lease_spec(**kw)
        if control:
            spec, sim = FAULTS[control](spec, sim)
    return BatchWorkload(spec=spec, config=sim, max_steps=cfg["max_steps"])


_gather = None


def sample(state, lanes) -> dict:
    """The sampled lanes' final arrays, on the host: one compiled gather of
    every field, then one transfer."""
    import jax

    global _gather
    if _gather is None:
        _gather = jax.jit(lambda arrays, idx: {f: a[idx]
                                               for f, a in arrays.items()})
    arrays = {f: getattr(state.node, f) for f in NODE_FIELDS}
    arrays.update({f: getattr(state, f) for f in LANE_FIELDS})
    if state.nem is not None:
        arrays.update({f: getattr(state.nem, f) for f in NEM_FIELDS})
    out = jax.device_get(_gather(arrays, np.asarray(lanes, np.int32)))
    out = {f: np.asarray(a).astype(np.int64) for f, a in out.items()}
    if state.nem is None:  # no clause compiled: no remove, no join
        out.update(reconfig_k=np.zeros(len(lanes), np.int64),
                   reconf_node=np.full(len(lanes), -1, np.int64))
    return out


def horizon_us(cfg: dict, traffic: dict) -> int:
    """The virtual time every lane of a sweep must reach."""
    return int({**cfg["sim"], **traffic.get("sim", {})}["horizon_us"])


def progress(s: dict):
    """Per sampled lane, the server's fencing token: the grants and
    renewals since the server's last wipe."""
    return s["l_token"][:, SERVER]


def reference(s: dict, seeds, horizon: int | None = None) -> list:
    """Per sampled lane, the guarantees its final state breaks (empty when
    sound). `seeds` are the lanes' seeds, in the same order. With
    `horizon`, the lane must also have run to it, and through at least
    the remove/join cycles the schedule completes by then."""
    from benchmark.lib.seeds import key_from_seed

    keys = key_from_seed(seeds)
    n = s["inc"].shape[1]
    out = []
    for lane in range(len(seeds)):
        broken = []
        if int(s["key0"][lane]) != int(keys[lane]):
            broken.append("not_this_seed")
        if horizon is not None:
            now = int(s["epoch"][lane]) * REBASE_US + int(s["clock"][lane])
            if not s["done"][lane] or s["deadlocked"][lane] or now < horizon:
                broken.append("short_of_horizon")
            if s["reconfig_k"][lane] < horizon // CYCLE_US:
                broken.append("membership_churn")
        if s["overflow"][lane] > 0:
            broken.append("pool_overflow")
        # membership: every node is a member but the one a remove took out
        # and its join has not yet brought back; each remove and each join
        # is one configuration change
        out_node = int(s["reconf_node"][lane])
        member = (int(s["member_p"][lane, 0]) >> np.arange(n)) & 1
        if (member != (np.arange(n) != out_node)).any():
            broken.append("membership_view")
        if s["member_epoch"][lane] != 2 * s["reconfig_k"][lane] \
                + (out_node >= 0):
            broken.append("membership_epoch")
        # the client the server records as holder, if it still believes:
        # held, and its expiry (on the lane's clock) not yet passed
        i = int(s["l_holder"][lane, SERVER])
        if 0 < i < s["held"].shape[1] and s["held"][lane, i] > 0 \
                and s["clock"][lane] <= s["my_expiry"][lane, i]:
            if s["l_inc"][lane, SERVER] != s["inc"][lane, i]:
                broken.append("incarnation_identity")
            if s["my_token"][lane, i] > s["l_token"][lane, SERVER]:
                broken.append("token_order")
            if s["my_expiry"][lane, i] > s["l_expiry"][lane, SERVER]:
                broken.append("expiry_order")
        out.append(broken)
    return out
