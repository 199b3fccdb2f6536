"""Ablation attribution of the step cost: time the full step, then steps
with one phase neutralized. Deltas rank where the milliseconds go.

Methodology: the shared measurement discipline (`madsim_tpu.measure`,
via the benches/measure.py shim) — on-device lax.scan chunks (per-step
host dispatch latency would drown the signal), fresh seeds derived per
rep index (every timed rep does new work), exact-program warmup,
medians over rounds (host contention is bursty).

Usage: PYTHONPATH=... python benches/ablate_step.py [--lanes 32768]
"""

from __future__ import annotations

import argparse
import dataclasses
import json

SCAN = 300


def measure(sim, lanes, rounds, warm_steps=200):
    """Median ms/step over `rounds` fresh-seed reps of a SCAN-step chunk
    (the shared discipline: measure.time_scan_ms)."""
    from measure import time_scan_ms

    return time_scan_ms(
        sim.init, sim.run_steps, lanes, scan=SCAN, warm_steps=warm_steps,
        rounds=rounds,
    )


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--lanes", type=int, default=32768)
    parser.add_argument("--rounds", type=int, default=3)
    args = parser.parse_args()

    import jax.numpy as jnp

    from madsim_tpu.tpu import BatchedSim, SimConfig, make_raft_spec
    from madsim_tpu.tpu.spec import Outbox

    def make(cfg_over=None, spec_over=None):
        spec = make_raft_spec(n_nodes=5, client_rate=0.1)
        if spec_over:
            from madsim_tpu.tpu.spec import replace_handlers

            spec = replace_handlers(spec, **spec_over)
        kw = dict(
            horizon_us=10_000_000,
            msg_capacity=128,
            loss_rate=0.10,
            crash_interval_lo_us=500_000,
            crash_interval_hi_us=3_000_000,
            restart_delay_lo_us=300_000,
            restart_delay_hi_us=2_000_000,
            partition_interval_lo_us=300_000,
            partition_interval_hi_us=1_500_000,
            partition_heal_lo_us=500_000,
            partition_heal_hi_us=2_000_000,
        )
        kw.update(cfg_over or {})
        return BatchedSim(spec, SimConfig(**kw))

    spec0 = make_raft_spec(n_nodes=5, client_rate=0.1)

    def id_on_message(s, nid, src, kind, payload, now, key):
        E = spec0.max_out_msg
        out = Outbox(
            valid=jnp.zeros((E,), jnp.bool_),
            dst=jnp.zeros((E,), jnp.int32),
            kind=jnp.zeros((E,), jnp.int32),
            payload=jnp.zeros((E, spec0.payload_width), jnp.int32),
        )
        return s, out, jnp.int32(-1)

    def id_on_timer(s, nid, now, key):
        E = spec0.max_out
        out = Outbox(
            valid=jnp.zeros((E,), jnp.bool_),
            dst=jnp.zeros((E,), jnp.int32),
            kind=jnp.zeros((E,), jnp.int32),
            payload=jnp.zeros((E, spec0.payload_width), jnp.int32),
        )
        return s, out, now + 50_000

    variants = {
        "full": make(),
        "no_invariants": make(
            spec_over={"check_invariants": lambda ns, alive, now: jnp.bool_(True)}
        ),
        "id_on_message": make(spec_over={"on_message": id_on_message}),
        "id_on_timer": make(spec_over={"on_timer": id_on_timer}),
        "id_both_handlers": make(
            spec_over={"on_message": id_on_message, "on_timer": id_on_timer}
        ),
        "no_chaos": make(
            cfg_over={"crash_interval_lo_us": 0, "crash_interval_hi_us": 0,
                      "partition_interval_lo_us": 0,
                      "partition_interval_hi_us": 0}
        ),
        "depth2": make(cfg_over={"msg_capacity": 300}),
    }

    med = {}
    for name, sim in variants.items():
        med[name] = measure(sim, args.lanes, args.rounds)
        print(
            json.dumps({
                "variant": name,
                "ms_per_step": round(med[name], 3),
                "delta_ms": round(med["full"] - med[name], 3),
            }),
            flush=True,
        )


if __name__ == "__main__":
    main()
