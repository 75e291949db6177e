"""Device milliseconds a step under the attention operator's scope over the
configuration's full-attention layers: rotary positions, the flash kernels
forward and backward (the recomputed forward included) and the head gate.
"""

import os
import runpy

DECODER = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "decoder_metrics.py"))

METRIC = {
    "name": "attention_full_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
    "workloads": ["laguna_xs2.seq8k"],
}


def read(run):
    return DECODER["attention_ms"](run, "laguna_xs2", "full_attention")
