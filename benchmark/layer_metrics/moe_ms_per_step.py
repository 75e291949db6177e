"""Device milliseconds a step of everything under the expert operator's
scope, forward and backward: router, top-k, sort and gathers, grouped
products (found by their own name: ``decoder_metrics.py``), combine and
the shared expert.
"""

import os
import runpy

DECODER = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "decoder_metrics.py"))

METRIC = {
    "name": "moe_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return DECODER["moe_ms"](run)
