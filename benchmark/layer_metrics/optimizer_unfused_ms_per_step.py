"""Device milliseconds a step of the instructions under the step's
``optimizer/update`` scope (``model.py`` ``_build_train_step``) that run
as instructions of their own: the UNFUSED part of the update, hence the
name. What XLA fuses into a weight-gradient convolution is named by that
convolution and is in ``backward_ms_per_step`` (``scopes.py``): a small
reading says the update is fused, not free, and a change that takes the
update out of those fusions moves time from there to here.
"""

import os
import runpy

SCOPES = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "scopes.py"))

METRIC = {
    "name": "optimizer_unfused_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "optimizer (optimizer.py; model.py optimizer/update)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return SCOPES["bucket_ms_per_step"](run, "optimizer_unfused")
