"""Summed ``setup.compile.trace`` of the train programs (``train_step:*``
labels) in set-up: turning the step into a jaxpr, where every operator's
Python and every Pallas kernel body runs. No compile cache holds it.
"""

import os
import runpy

SETUP = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "setup_spans.py"))

METRIC = {
    "name": "train_step_trace_s",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "layer": "step builder + compile management (_build_train_step, utils/compile.py)",
    "moves": "setup_s",
}


def read(run):
    return SETUP["reading"](run, METRIC["name"])
