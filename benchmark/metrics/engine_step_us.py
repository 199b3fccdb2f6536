"""Device time per iteration of the engine's sweep loop over the profiled
calls: the time of the jitted `_run` programs in the trace, over the
loop's iterations, which are the sum over the calls' chunks of each
chunk's longest lane (its largest retired step)."""

TRACED_PROGRAM = "_run"  # BatchedSim._run, tpu/engine.py


def read(run):
    if run.trace is None:
        return None
    traced = run.records[:int(run.cell.traffic.get("trace_calls", 1))]
    steps = sum(r["loop_steps"] for r in traced)
    ns = run.trace.program_ns(TRACED_PROGRAM)
    return ns / steps / 1e3 if ns and steps else None
