"""Device milliseconds a step of everything the state-space layers' nodes
do, every Mamba-2 layer, forward and backward: the input projection and
the split of its columns, the causal convolution, the chunked scan, the
gated norm, the output projection (``ssm_costs.SCOPE``). Nothing where the
program has no such layer.
"""

import os
import runpy

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = runpy.run_path(os.path.join(HERE, "scopes.py"))
COSTS = runpy.run_path(os.path.join(HERE, "ssm_costs.py"))

METRIC = {
    "name": "ssm_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return SCOPES["ms_per_step"](run, COSTS["SCOPE"])
