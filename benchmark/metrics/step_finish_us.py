"""Device us per engine-loop iteration of the step's `finish` phase: the
ops of the jitted `_run` programs under the named scope `step/finish`
(tpu/engine.py::STEP_PHASES), over the iterations `engine_step_us`
counts. A fused op counts in the phase of its root instruction."""

from benchmark.lib import scopes


def read(run):
    return scopes.step_us(run, "finish")
