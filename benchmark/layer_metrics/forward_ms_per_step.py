"""Device milliseconds a step of the instructions the forward pass made: those
whose scope in the train program's HLO is under ``jvp(...)`` and not under
its transpose (every operator of the graph, emitted as ``<node>/<Operator>``
by the executor, the parameters' cast to the compute type and ``loss/sum``
with them). Each instruction has the one scope ``scopes.py`` reads for it.
"""

import os
import runpy

SCOPES = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "scopes.py"))

METRIC = {
    "name": "forward_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return SCOPES["bucket_ms_per_step"](run, "forward")
