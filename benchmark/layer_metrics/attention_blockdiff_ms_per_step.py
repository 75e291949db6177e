"""Device milliseconds a step of everything under the block-diffusion
attention operator's scope, every layer, forward and backward: the keys'
rotation, the flash kernels over the noisy and the clean copy (the
recomputed forward included). Nothing where the program has no such
operator.
"""

import os
import runpy

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = runpy.run_path(os.path.join(HERE, "scopes.py"))
COSTS = runpy.run_path(os.path.join(HERE, "blockdiff_costs.py"))

METRIC = {
    "name": "attention_blockdiff_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return SCOPES["ms_per_step"](run, COSTS["SCOPE"])
