"""The flash kernels of the sliding-window layers of the cell's own
configuration (``run["config"]``: its heads, widths, length and window)
against their roofline:
``max(FLOP / peak, bytes / bandwidth)`` of forward and backward once each
(``kernel_costs.py``: the products inside the window only) over the time
the kernels took in a step, the recomputed forward in the time alone.
"""

import os
import runpy

DECODER = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "decoder_metrics.py"))

METRIC = {
    "name": "attention_window_roofline_pct",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return DECODER["attention_roofline_pct"](run, DECODER["SLIDING"])
