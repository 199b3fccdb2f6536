"""Milliseconds per call of the workload's host oracle (`lane_check`,
tpu/linearize.py), timed by the harness's wrapper over the window."""


def read(run):
    s = run.oracle_s
    return 1e3 * sum(s) / len(s) if s else None
