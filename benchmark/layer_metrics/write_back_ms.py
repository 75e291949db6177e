"""Median over the traced epochs of ``fit.epoch.write_back``: ``fit`` copying
every parameter and auxiliary state to the host at the epoch's end, the
one stretch of the tail where a pause of the host is paid in full.
"""

import os
import runpy

SPANS = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "span_reduce.py"))

METRIC = {
    "name": "write_back_ms",
    "unit": "ms",
    "better": "lower",
    "source": "program_span",
    "layer": "entry / epoch loop (model.py fit)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return SPANS["reading"](run, METRIC["name"])
