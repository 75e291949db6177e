"""Summed ``setup.init_params``: shape inference and the initializer, array
by array on the host, in ``precompile`` and again (nothing left to do) in
``fit``.
"""

import os
import runpy

SPANS = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "span_reduce.py"))

METRIC = {
    "name": "init_params_s",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "layer": "set-up (model.py _init_params, precompile, fit start)",
    "moves": "setup_s",
}


def read(run):
    return SPANS["reading"](run, METRIC["name"])
