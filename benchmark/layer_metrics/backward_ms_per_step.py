"""Device milliseconds a step of the instructions the backward pass made: those
whose scope in the train program's HLO is under ``transpose(jvp(...))``,
what ``jax.value_and_grad`` transposes of the graph's operators. XLA names
a fusion by the product in it, so a weight-gradient convolution that
carries the optimizer's update as its epilogue counts here (``scopes.py``).
"""

import os
import runpy

SCOPES = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "scopes.py"))

METRIC = {
    "name": "backward_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return SCOPES["bucket_ms_per_step"](run, "backward")
