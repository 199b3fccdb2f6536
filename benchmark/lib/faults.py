"""Faults planted under the timed path, by name, for any configuration:
`run.py --control NAME` and the tests put one in the program's place to
show that the comparison reads it as not correct (PERF.md, correctness).

Each takes the program's (spec, SimConfig) and returns the pair with the
fault in it."""

from __future__ import annotations

import dataclasses


def deliveries_lost(spec, sim):
    """Every delivered message is silently lost inside the step: the node
    neither changes state nor replies, and no drop is counted. Timers still
    fire, so lanes run to the horizon with nothing to show for it."""
    import jax
    import jax.numpy as jnp

    from madsim_tpu.tpu.spec import wraps_event

    real = spec.on_event

    def lost(s, nid, src, kind, payload, now, key):
        state, out, timer = real(s, nid, src, kind, payload, now, key)
        msg = kind >= 0
        state = jax.tree_util.tree_map(
            lambda a, b: jnp.where(msg, a, b), s, state)
        return (state, out._replace(valid=out.valid & ~msg),
                jnp.where(msg, jnp.int32(-1), timer))

    on_timer = wraps_event(lost)(
        lambda s, nid, now, key: spec.on_timer(s, nid, now, key))
    on_message = wraps_event(lost)(lost)
    return dataclasses.replace(spec, on_event=lost, on_message=on_message,
                               on_timer=on_timer), sim


def half_horizon(spec, sim):
    """The engine retires every lane at half the configured horizon."""
    return spec, dataclasses.replace(sim, horizon_us=sim.horizon_us // 2)


def false_alarm(spec, sim):
    """The device check fires on a sound state: as soon as a node has
    committed an entry (no guarantee is broken there)."""
    import jax.numpy as jnp

    return dataclasses.replace(
        spec, check_invariants=lambda ns, alive, now: ~jnp.any(
            ns.commit >= 0)), sim


FAULTS = {"deliveries_lost": deliveries_lost, "half_horizon": half_horizon,
          "false_alarm": false_alarm}
