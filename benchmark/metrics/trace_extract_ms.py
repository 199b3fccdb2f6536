"""Milliseconds per bundle of the violation microscope's `extract[trace]`
spans (the host decode of the record into events; tpu/trace.py::trace_seed), summed over the window, as
`shrink_ms` counts."""

from benchmark.lib import scopes


def read(run):
    return scopes.per_bundle_ms(run, "extract[trace]")
