"""Milliseconds per bundle of the violation microscope's `scan[trace]`
spans (the traced replay on the device, to its last record; tpu/trace.py::trace_seed), summed over the window, as
`shrink_ms` counts."""

from benchmark.lib import scopes


def read(run):
    return scopes.per_bundle_ms(run, "scan[trace]")
