"""Device us per engine-loop iteration of the step's `network` phase: the
ops of the jitted `_run` programs under the named scope `step/network`
(tpu/engine.py::STEP_PHASES), over the iterations `engine_step_us`
counts. A fused op counts in the phase of its root instruction."""

from benchmark.lib import scopes


def read(run):
    return scopes.step_us(run, "network")
