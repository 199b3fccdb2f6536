"""Device us per engine-loop iteration of the step's `chaos` phase: the
ops of the jitted `_run` programs under the named scope `step/chaos`
(tpu/engine.py::STEP_PHASES), over the iterations `engine_step_us`
counts. A fused op counts in the phase of its root instruction."""

from benchmark.lib import scopes


def read(run):
    return scopes.step_us(run, "chaos")
