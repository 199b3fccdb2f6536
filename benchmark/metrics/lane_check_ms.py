"""Milliseconds per call of the workload's deep host oracle, as the
program times it: the `lane_check[run_batch]` spans of the window. The
hook it times also holds the harness's sampling, so it reads at least
`oracle_ms`."""

from benchmark.lib import scopes


def read(run):
    return scopes.mean_ms(run, "lane_check[run_batch]")
