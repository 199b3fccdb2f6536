"""Device us per engine-loop iteration of the `_run` ops under no step
phase (layout copies between iterations, the loop's condition and
control), over the iterations `engine_step_us` counts."""

from benchmark.lib import scopes


def read(run):
    return scopes.step_us(run, scopes.UNSCOPED)
