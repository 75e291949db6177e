"""``fit.start``: the entry of ``fit`` to its first epoch (iterator, kvstore,
mesh, placement of parameters, optimizer state).
"""

import os
import runpy

SPANS = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "span_reduce.py"))

METRIC = {
    "name": "fit_start_s",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "layer": "set-up (model.py _init_params, precompile, fit start)",
    "moves": "setup_s",
}


def read(run):
    return SPANS["reading"](run, METRIC["name"])
