"""``setup.precompile`` less what its children cover
(``setup.init_params``, ``setup.build_step``, ``setup.compile``, the
compiles made right under it): the part of ``precompile`` no span names.
"""

import os
import runpy

SETUP = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "setup_spans.py"))

METRIC = {
    "name": "precompile_unnamed_s",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "layer": "set-up (model.py _init_params, precompile, fit start)",
    "moves": "setup_s",
}


def read(run):
    return SETUP["reading"](run, METRIC["name"])
