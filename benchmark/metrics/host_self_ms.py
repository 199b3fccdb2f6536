"""Milliseconds of the host's own work per run_batch call: each
`run_batch[chunked]` span of the window, minus the part of it that its
`wait[segment]`, `wait[run_batch]` and `lane_check[run_batch]`
descendants cover (waiting on the device, and the workload's oracle
hook), averaged over the calls."""

from benchmark.lib import scopes


def read(run):
    return scopes.host_self_ms(run, "run_batch[chunked]",
                               ("wait", "lane_check"))
