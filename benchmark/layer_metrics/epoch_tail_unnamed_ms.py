"""``epoch_tail_host_ms`` less the part of it that ``fit``'s named spans
cover (feed start and close, feed wait, dispatch, metric pull, checkpoint,
write-back, evaluation): what the spans do not explain yet.
"""

import os
import runpy

SPANS = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "span_reduce.py"))

METRIC = {
    "name": "epoch_tail_unnamed_ms",
    "unit": "ms",
    "better": "lower",
    "source": "program_span",
    "layer": "entry / epoch loop (model.py fit)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return SPANS["reading"](run, METRIC["name"])
