"""A traced epoch's tail as the host sees it: the end of the previous
``fit.epoch.callback`` to the end of the epoch's first ``fit.dispatch``,
plus the end of ``fit.epoch.drain`` (the device has finished the last
step) to the start of the next ``fit.epoch.callback``. Median over the
traced epochs. ``epoch_tail_ms`` times the same stretch from outside.
"""

import os
import runpy

SPANS = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "span_reduce.py"))

METRIC = {
    "name": "epoch_tail_host_ms",
    "unit": "ms",
    "better": "lower",
    "source": "program_span",
    "layer": "entry / epoch loop (model.py fit)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return SPANS["reading"](run, METRIC["name"])
