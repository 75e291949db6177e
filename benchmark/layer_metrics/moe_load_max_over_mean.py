"""Over the experts held here, the largest count of picks over the mean,
from the traced epochs' ``fit.epoch.expert_load`` records, the worst node.
"""

import os
import runpy

DECODER = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "decoder_metrics.py"))

METRIC = {
    "name": "moe_load_max_over_mean",
    "unit": "ratio",
    "better": "lower",
    "source": "program_span",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return DECODER["moe_load_max_over_mean"](run)
