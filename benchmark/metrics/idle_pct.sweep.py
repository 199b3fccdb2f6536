"""Idle share of the most idle device over the profiled sweep calls (%)."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share()
