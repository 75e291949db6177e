"""Low decile over the traced steps of ``fit.step`` (the feed wait lies
outside it): the host's own work a step. The low decile because a dispatch
blocks once the runtime's queue of in-flight steps is full, so the median
reads the device's step. How far ``device_step_ms`` can fall before ``fit``
is host-bound.
"""

import os
import runpy

SPANS = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "span_reduce.py"))

METRIC = {
    "name": "host_step_ms_p10",
    "unit": "ms",
    "better": "lower",
    "source": "program_span",
    "layer": "entry / epoch loop (model.py fit)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return SPANS["reading"](run, METRIC["name"])
