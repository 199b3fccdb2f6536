"""Etcd-family lease/watch (the seventh device protocol) — the house
test pattern from docs/authoring_protocol_specs.md: safety under the
chaos battery, determinism, the planted canonical bug caught (on BOTH
faces, and ONLY via the membership axis: the durable incarnation nonce
makes plain crash/restart invisible to the server), and host-twin
wiring."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu.tpu import BatchedSim, lease_workload, make_lease_spec, summarize
from madsim_tpu.workloads import lease_host


def test_lease_safety_under_chaos_battery():
    wl = lease_workload(virtual_secs=5.0)
    sim = BatchedSim(wl.spec, wl.config)
    state = sim.run(jnp.arange(256), max_steps=30_000)
    s = summarize(state, wl.spec)
    assert s["violations"] == 0
    assert s["total_overflow"] == 0
    # progress: the fencing token advances (leases are granted/renewed)
    assert s["mean_lease_token"] > 2


def test_lease_determinism():
    wl = lease_workload(virtual_secs=2.0)
    sim = BatchedSim(wl.spec, wl.config)
    a = sim.run(jnp.arange(32), max_steps=10_000)
    b = sim.run(jnp.arange(32), max_steps=10_000)
    for x, y in zip(
        __import__("jax").tree_util.tree_leaves(a.node),
        __import__("jax").tree_util.tree_leaves(b.node),
    ):
        assert (np.asarray(x) == np.asarray(y)).all()


def test_zombie_lease_bug_fires_only_via_membership_axis():
    """The canonical planted bug: the server matches a renewal by node id
    alone, ignoring the incarnation. Crash/restart carries the durable
    nonce, so the renewal legitimately matches — ONLY a wipe-join (the
    reconfig clause's remove -> fresh join) rotates the incarnation and
    turns the old one's lease into a zombie the fresh client keeps
    renewing."""
    wl = lease_workload(virtual_secs=10.0)
    buggy = make_lease_spec(5, buggy_zombie_lease=True)

    # crash/restart only: the nonce survives, id-only matching is
    # indistinguishable from the correct rule — the bug CANNOT fire
    quiet_cfg = dataclasses.replace(
        wl.config,
        nem_reconfig_interval_lo_us=0, nem_reconfig_interval_hi_us=0,
    )
    state = BatchedSim(buggy, quiet_cfg).run(jnp.arange(128), max_steps=40_000)
    assert summarize(state)["violations"] == 0

    # membership churn rotates incarnations: the zombie appears
    state = BatchedSim(buggy, wl.config).run(jnp.arange(128), max_steps=40_000)
    with_churn = summarize(state)["violations"]
    assert with_churn > 16

    # control: the incarnation-checking spec is clean under identical churn
    state = BatchedSim(wl.spec, wl.config).run(jnp.arange(128), max_steps=40_000)
    assert summarize(state)["violations"] == 0


def test_lease_host_twin_clean_and_bug_on_both_faces():
    r = lease_host.fuzz_one_seed(0, virtual_secs=6.0)
    assert r["final_token"] > 0

    # host face: pinned violating seed (sweep 0..11 hit 0/2/5/6/7/8/11)
    with pytest.raises(lease_host.InvariantViolation):
        lease_host.fuzz_one_seed(0, virtual_secs=10.0, buggy=True)
    # the correct protocol is clean under the SAME chaos and seed
    lease_host.fuzz_one_seed(0, virtual_secs=10.0)

    # workload wiring: host_repro present and runs end to end
    out = lease_workload(virtual_secs=4.0).host_repro(4)
    assert out["violations"] == 0


def test_lease_at_the_etcd_deployment_s_ttl_and_keepalive():
    """The benchmark's lease5 spec (etcd's 1.5 s TTL floor, clientv3's
    TTL/3 keepalive) under lease_workload's chaos: clean, no pool
    overflow, and the Reconfig clause fires."""
    wl = lease_workload(virtual_secs=10.0)
    spec = make_lease_spec(5, ttl_us=1_500_000, ka_interval_us=500_000)
    state = BatchedSim(spec, wl.config).run(jnp.arange(256), max_steps=100_000)
    s = summarize(state, spec)
    assert s["violations"] == 0 and s["total_overflow"] == 0
    assert s["fires_remove"] > 0 and s["fires_join"] > 0
    assert np.asarray(state.done).all()
