"""Replay a triage repro bundle: `python -m madsim_tpu.repro bundle.json`.

The counterpart of `madsim_tpu/triage.py`: a bundle is only worth shipping
in a bug report if a fresh process — with no access to the sweep that found
it — replays the violation bit-deterministically. This module is that
check, as a library (`replay`) and a CLI:

    python -m madsim_tpu.repro bundle.json                 # device replay
    python -m madsim_tpu.repro bundle.json --backend host  # schedule twin
    python -m madsim_tpu.repro bundle.json --trace 60      # + event tail

Device replay (`--backend tpu`, the default) rebuilds the ProtocolSpec from
the bundle's `spec_ref`, the SimConfig from its TOML (hash-checked), runs
the seed under the bundle's shrink ctl TWICE, asserts the two final states
are bitwise identical, and asserts the violation fires at the recorded
step and virtual time.

Host replay (`--backend host`) drives the bundle's SHRUNK FaultPlan through
a fresh host runtime's NemesisDriver (idle nodes; the schedule needs no
traffic) and asserts the applied fault stream equals the occurrence-filtered
pure schedule — the twin invariant, surviving the shrink.

Divergence bundles (`violation_kind == "divergence"`, written by
madsim_tpu/oracle.py) are inherently differential, so EVERY backend choice
routes to the oracle replay: the shrunk plan re-runs schedule-matched on
the host twin `--repeats` times, each run must reproduce the SAME first
divergent event bit-identically (same site/index/applied/expected, same
state digest), and the bundle's v3 `causal` digest is cross-checked
against the replayed host slice. A reproduced divergence prints the
readable first-divergent-event report and the CLI exits NON-ZERO — the
two backends still disagree, which is a live bug, not a clean replay.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys
from typing import Any, Dict, List, Optional

from .triage import ReproBundle


class ReplayError(AssertionError):
    """The bundle did not replay as recorded."""


def resolve_spec(spec_ref: str, spec_kwargs: Optional[Dict[str, Any]] = None):
    """Rebuild a ProtocolSpec from a dotted "module:factory" reference."""
    mod_name, _, fn_name = spec_ref.partition(":")
    if not mod_name or not fn_name:
        raise ValueError(
            f"spec_ref must look like 'package.module:factory', got {spec_ref!r}"
        )
    # bundles written inside a checkout reference test modules by their
    # repo-relative dotted path; make the common case work from anywhere.
    # Remove the exact entry we added (not pop(0)): the spec module's own
    # import may mutate sys.path, and a positional pop would evict it.
    cwd = os.getcwd()
    sys.path.insert(0, cwd)
    try:
        mod = importlib.import_module(mod_name)
    finally:
        try:
            sys.path.remove(cwd)
        except ValueError:
            pass
    return getattr(mod, fn_name)(**(spec_kwargs or {}))


def replay_device(
    bundle: ReproBundle,
    spec=None,
    repeats: int = 2,
    trace: int = 0,
    perfetto: Optional[str] = None,
    explain: int = 0,
    out=print,
) -> Dict[str, Any]:
    """Device replay: the violation must fire at the recorded step/time,
    bit-identically across `repeats` runs. Returns a report dict.

    `trace=N` prints the last N trace events; `perfetto=PATH` additionally
    writes the FULL replayed trajectory as a Chrome-trace/Perfetto
    timeline (madsim_tpu.telemetry.write_perfetto) — one track per node,
    deliveries as src→dst flow arrows, chaos windows as slices, the
    violation as an instant marker. `explain=N` replays the bundle once
    more with the causal-lineage plane on (BatchedSim(lineage=True)) and
    prints the last N links of the violation's minimal causal slice —
    the chain of deliveries/timer fires the violation transitively
    depends on (docs/causality.md); when the bundle carries a v3 causal
    digest, the replayed slice's label sha is cross-checked against it
    (schema drift fails loudly, like the config hash)."""
    import jax
    import numpy as np

    from .tpu.engine import BatchedSim
    from .tpu.spec import REBASE_US

    if spec is None:
        if not bundle.spec_ref:
            raise ReplayError(
                "bundle has no spec_ref — pass the ProtocolSpec explicitly "
                "(replay_device(bundle, spec=...)) or re-emit the bundle "
                "with shrink_seed(spec_ref=...)"
            )
        spec = resolve_spec(bundle.spec_ref, bundle.spec_kwargs)
    if spec.n_nodes != bundle.n_nodes:
        raise ReplayError(
            f"spec has {spec.n_nodes} nodes, bundle recorded {bundle.n_nodes}"
        )
    cfg = bundle.config()  # hash-checked
    sim = BatchedSim(spec, cfg, triage=True)
    ctl = bundle.ctl(1)
    states = [
        sim.run([bundle.seed], max_steps=bundle.max_steps, ctl=ctl)
        for _ in range(max(1, repeats))
    ]
    a = states[0]
    for i, b in enumerate(states[1:], start=2):
        la = jax.tree_util.tree_leaves(a)
        lb = jax.tree_util.tree_leaves(b)
        for j, (x, y) in enumerate(zip(la, lb)):
            if not np.array_equal(np.asarray(x), np.asarray(y)):
                raise ReplayError(
                    f"replay {i} diverged from replay 1 at state leaf {j} — "
                    "the device stream is not bit-deterministic"
                )
    violated = bool(np.asarray(a.violated)[0])
    step = int(np.asarray(a.violation_step)[0])
    t_us = int(
        np.asarray(a.violation_epoch, np.int64)[0] * REBASE_US
        + np.asarray(a.violation_at, np.int64)[0]
    )
    if not violated:
        raise ReplayError(
            f"seed {bundle.seed} did NOT violate under the bundle's shrunk "
            "configuration — stale bundle or schema drift"
        )
    if step != bundle.violation_step or t_us != bundle.violation_t_us:
        raise ReplayError(
            f"violation replayed at step {step} / t={t_us}us but the bundle "
            f"recorded step {bundle.violation_step} / "
            f"t={bundle.violation_t_us}us"
        )
    if trace > 0 or perfetto:
        from .tpu.trace import trace_seed

        events = trace_seed(
            sim, bundle.seed, max_steps=step + 2,
            kind_names=spec.msg_kind_names, ctl=ctl,
        )
        for e in events[-trace:] if trace > 0 else []:
            out(str(e))
        if perfetto:
            from . import telemetry

            telemetry.write_perfetto(
                perfetto, events, n_nodes=spec.n_nodes,
                label=f"{bundle.spec_name} seed {bundle.seed}",
            )
            out(f"perfetto timeline: {perfetto}")
    rep = {"violated": True, "step": step, "t_us": t_us, "repeats": repeats}
    if explain > 0:
        from . import causal

        g, sl = causal.explain(
            spec, cfg, bundle.seed, ctl=ctl, max_steps=step + 2,
        )
        digest = causal.causal_digest(sl)
        tail = (
            causal.causal_slice(g, max_len=explain)
            if len(sl.chain) > explain else sl
        )
        out(causal.format_slice(tail))
        if bundle.causal is not None and (
            bundle.causal.get("sha") != digest["sha"]
        ):
            raise ReplayError(
                "causal slice diverged from the bundle's recorded digest "
                f"({digest['sha']} != {bundle.causal.get('sha')}) — the "
                "lineage plane or the slice semantics drifted"
            )
        rep["causal"] = digest
    out(
        f"device replay OK: seed {bundle.seed} violates at step {step}, "
        f"t={t_us}us, bit-identical across {max(1, repeats)} runs"
    )
    if bundle.signature:
        # campaign provenance (bundle schema v2): the dedup signature keys
        # this bug class across seeds/campaigns — docs/campaign.md
        provenance = ""
        if bundle.campaign is not None:
            provenance = f" (campaign {bundle.campaign}"
            if bundle.generation is not None:
                provenance += f", generation {bundle.generation}"
            provenance += ")"
        out(f"bug signature: {bundle.signature}{provenance}")
        rep["signature"] = bundle.signature
    return rep


def replay_host(bundle: ReproBundle, out=print) -> Dict[str, Any]:
    """Host schedule twin: a fresh runtime's NemesisDriver applies exactly
    the shrunk plan's occurrence-filtered pure schedule."""
    import madsim_tpu as ms
    from .nemesis import NemesisDriver, filter_schedule

    plan = bundle.shrunk_plan()
    horizon_us = int(bundle.horizon_us)
    n = int(bundle.n_nodes)

    async def body():
        handle = ms.Handle.current()

        async def idle():
            while True:
                await ms.time.sleep(3600.0)

        nodes = [
            handle.create_node().name(f"r{i}").ip(f"10.9.9.{i + 1}")
            .init(idle).build()
            for i in range(n)
        ]
        driver = NemesisDriver(
            plan, handle, [nd.id for nd in nodes], horizon_us=horizon_us,
            seed=bundle.seed, occ_off=bundle.occ_off,
        )
        driver.install()
        t = ms.time.current()
        end = t.elapsed() + horizon_us / 1e6 + 0.001
        while t.elapsed() < end:
            await ms.time.sleep(0.05)
        return driver

    rt = ms.Runtime(seed=bundle.seed)
    driver = rt.block_on(body())
    want = [
        e for e in filter_schedule(
            plan.schedule(bundle.seed, horizon_us, n), bundle.occ_off
        )
        if e.kind != "skew"  # applied at install time, not replayed
    ]
    got = list(driver.applied)
    if got != want:
        raise ReplayError(
            "host driver stream diverged from the shrunk pure schedule:\n"
            f"  want ({len(want)}): {[str(e) for e in want]}\n"
            f"  got  ({len(got)}): {[str(e) for e in got]}"
        )
    out(
        f"host schedule twin OK: {len(want)} shrunk fault events applied "
        "exactly as scheduled"
    )
    return {"events": len(want)}


def replay_divergence(
    bundle: ReproBundle, repeats: int = 2, out=print,
) -> Dict[str, Any]:
    """Replay a host/device divergence bundle (madsim_tpu/oracle.py):
    re-run the shrunk plan schedule-matched on the host twin `repeats`
    times and assert the SAME first divergent event reproduces
    bit-identically every time. Raises ReplayError when the lane no
    longer diverges (stale bundle / fixed tree) or when repeats disagree
    (the replay itself is nondeterministic — a worse bug). Returns a
    report with `diverged=True`; callers treat that as a failing exit,
    because a reproduced divergence means the backends still disagree."""
    from . import oracle

    plan = bundle.shrunk_plan()
    horizon_us = int(bundle.horizon_us)
    n = int(bundle.n_nodes)
    loss_rate = 0.1
    if bundle.config_toml:
        loss_rate = float(getattr(bundle.config(), "loss_rate", 0.1))
    repeats = max(1, repeats)
    reps = [
        oracle.check_seed(
            bundle.spec_name, plan, bundle.seed, horizon_us, n_nodes=n,
            loss_rate=loss_rate, occ_off=bundle.occ_off, repeats=1,
        )
        for _ in range(repeats)
    ]
    for i, rep in enumerate(reps, start=1):
        if not rep.diverged:
            raise ReplayError(
                f"replay {i}: seed {bundle.seed} did NOT diverge under the "
                "bundle's shrunk plan — stale bundle, or the host/device "
                "skew it recorded has been fixed"
            )

    def ident(r):
        d = r.first
        return (d.kind, d.site, d.index, d.applied, d.expected, d.eid,
                r.digest, len(r.divergences))

    first = reps[0]
    for i, rep in enumerate(reps[1:], start=2):
        if ident(rep) != ident(first):
            raise ReplayError(
                "divergence replay is not bit-deterministic: replay "
                f"{i} reproduced {ident(rep)} but replay 1 gave "
                f"{ident(first)}"
            )
    d = first.first
    if bundle.causal is not None and d.slice_digest is not None and (
        bundle.causal.get("sha") != d.slice_digest.get("sha")
    ):
        raise ReplayError(
            "host causal slice diverged from the bundle's recorded digest "
            f"({d.slice_digest.get('sha')} != {bundle.causal.get('sha')}) — "
            "the lineage plane or the slice semantics drifted"
        )
    out(first.render())
    out(
        f"divergence reproduced bit-identically across {repeats} "
        "schedule-matched host replays — the backends still disagree"
    )
    return {
        "diverged": True,
        "repeats": repeats,
        "first": d.to_dict(),
        "digest": first.digest,
    }


def replay(
    bundle: ReproBundle, backend: str = "tpu", spec=None, repeats: int = 2,
    trace: int = 0, perfetto: Optional[str] = None, explain: int = 0,
    out=print,
) -> Dict[str, Any]:
    if bundle.violation_kind == "divergence":
        # differential by construction: there is no single-backend replay
        # of a host-vs-device divergence, so tpu/host/both all route here
        return replay_divergence(bundle, repeats=repeats, out=out)
    if backend == "tpu":
        return replay_device(
            bundle, spec=spec, repeats=repeats, trace=trace,
            perfetto=perfetto, explain=explain, out=out,
        )
    if backend == "host":
        return replay_host(bundle, out=out)
    if backend == "both":
        rep = replay_device(
            bundle, spec=spec, repeats=repeats, trace=trace,
            perfetto=perfetto, explain=explain, out=out,
        )
        rep.update(replay_host(bundle, out=out))
        return rep
    raise ValueError(f"unknown backend {backend!r} (tpu|host|both)")


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m madsim_tpu.repro",
        description="Replay a triage repro bundle and assert the violation "
        "still fires (see docs/triage.md).",
    )
    p.add_argument("bundle", help="path to a repro bundle JSON")
    p.add_argument(
        "--backend", choices=("tpu", "host", "both"), default="tpu",
        help="tpu: replay the violation on the batched engine; host: assert "
        "the shrunk plan's schedule twin on the host runtime",
    )
    p.add_argument(
        "--spec-ref", default=None,
        help="override the bundle's 'module:factory' ProtocolSpec reference",
    )
    p.add_argument(
        "--repeats", type=int, default=2,
        help="device replays to compare bitwise (default 2)",
    )
    p.add_argument(
        "--trace", type=int, default=0, metavar="N",
        help="print the last N trace events of the replayed violation",
    )
    p.add_argument(
        "--perfetto", nargs="?", const="", default=None, metavar="PATH",
        help="write the replayed trajectory as a Chrome-trace/Perfetto "
        "timeline; with no PATH it lands next to the bundle "
        "(<bundle>.perfetto.json). Device replay only.",
    )
    p.add_argument(
        "--explain", nargs="?", const=20, type=int, default=0, metavar="N",
        help="replay once more with the causal-lineage plane on and print "
        "the last N links (default 20) of the violation's minimal causal "
        "slice — the happens-before chain it depends on (docs/causality"
        ".md). Cross-checks the bundle's v3 causal digest when present. "
        "Device replay only.",
    )
    args = p.parse_args(argv)
    from .compile_cache import configure_compile_cache

    configure_compile_cache()
    bundle = ReproBundle.load(args.bundle)
    if args.spec_ref:
        bundle.spec_ref = args.spec_ref
    perfetto = args.perfetto
    if perfetto == "":
        # default: next to the bundle, so the timeline ships with it
        root, _ = os.path.splitext(args.bundle)
        perfetto = f"{root}.perfetto.json"
    try:
        rep = replay(
            bundle, backend=args.backend, repeats=args.repeats,
            trace=args.trace, perfetto=perfetto, explain=args.explain,
        )
    except (ReplayError, ValueError) as e:
        print(f"REPLAY FAILED: {e}", file=sys.stderr)
        return 1
    if rep.get("diverged"):
        # the divergence reproduced — that's a live host-vs-device bug,
        # so the CLI fails even though the replay itself succeeded
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
