"""Summed ``compile.backend`` records of every program but the train
programs before the window: the initializers, casts, placement, the
metric, the feeder's programs (each XLA compile or persistent-cache read
outside ``TrackedJit.precompile`` leaves one).
"""

import os
import runpy

SETUP = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "setup_spans.py"))

METRIC = {
    "name": "setup_other_compile_s",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "layer": "set-up (model.py _init_params, precompile, fit start)",
    "moves": "setup_s",
}


def read(run):
    return SETUP["reading"](run, METRIC["name"])
