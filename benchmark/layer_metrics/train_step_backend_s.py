"""Summed ``setup.compile.backend`` of the train programs in set-up: XLA's
compile of the lowered step, or its read from the persistent compile cache
(the span's ``cache`` attr says which: a load when warm).
"""

import os
import runpy

SETUP = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "setup_spans.py"))

METRIC = {
    "name": "train_step_backend_s",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "layer": "step builder + compile management (_build_train_step, utils/compile.py)",
    "moves": "setup_s",
}


def read(run):
    return SETUP["reading"](run, METRIC["name"])
