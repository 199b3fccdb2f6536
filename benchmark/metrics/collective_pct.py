"""Share of the busiest device's busy time in collective ops (the sharded
while-loop's all-reduce of `any(~done)`), over the profiled calls (%)."""


def read(run):
    if run.trace is None:
        return None
    share = run.trace.collective_share()
    return None if share is None else 100.0 * share
