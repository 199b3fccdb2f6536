"""Milliseconds per bundle of the violation microscope's `fetch[trace]`
spans (the one transfer of the replay's record to the host; tpu/trace.py::trace_seed), summed over the window, as
`shrink_ms` counts."""

from benchmark.lib import scopes


def read(run):
    return scopes.per_bundle_ms(run, "fetch[trace]")
