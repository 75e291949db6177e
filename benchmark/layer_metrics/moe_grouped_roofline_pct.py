"""The expert layers' grouped products against their roofline at the rows a
step of the traced epochs really routed here (the program's
``fit.epoch.expert_load`` records) and the widths of the cell's own
configuration (``run["config"]``), forward and backward once each, over the
time the grouped products took in a step.
"""

import os
import runpy

DECODER = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "decoder_metrics.py"))

METRIC = {
    "name": "moe_grouped_roofline_pct",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return DECODER["moe_grouped_roofline_pct"](run)
