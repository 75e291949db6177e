"""Device milliseconds a step under the attention operator's scope over the
sliding-window layers of the cell's own configuration (``run["config"]``):
rotary positions, the flash kernels forward and backward (the recomputed
forward included) and the head gate where it has one.
"""

import os
import runpy

DECODER = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "decoder_metrics.py"))

METRIC = {
    "name": "attention_window_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return DECODER["attention_ms"](run, DECODER["SLIDING"])
