"""Device milliseconds a step of every instruction that is in none of forward,
backward and optimizer: no scope in the HLO (asynchronous copies, layout
changes of the parameters), the step's own ``metric/update``, ``guards/*``
and ``comm/allreduce``, and the instructions of other programs in the
traced span. The four add up to the summed instruction time of a step.
"""

import os
import runpy

SCOPES = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "scopes.py"))

METRIC = {
    "name": "unscoped_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return SCOPES["bucket_ms_per_step"](run, "unscoped")
