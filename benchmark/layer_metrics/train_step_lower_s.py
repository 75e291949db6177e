"""Summed ``setup.compile.lower`` of the train programs in set-up: the
jaxpr lowered to StableHLO with the Mosaic payloads of the kernels. No
compile cache holds it either.
"""

import os
import runpy

SETUP = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "setup_spans.py"))

METRIC = {
    "name": "train_step_lower_s",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "layer": "step builder + compile management (_build_train_step, utils/compile.py)",
    "moves": "setup_s",
}


def read(run):
    return SETUP["reading"](run, METRIC["name"])
