"""A traced epoch's summed ``fit.feed_wait`` over its steps, the first batch
after the feed restarts included; median over the traced epochs.
"""

import os
import runpy

SPANS = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "span_reduce.py"))

METRIC = {
    "name": "feed_wait_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "program_span",
    "layer": "input feed (io/, _AsyncDeviceFeed)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return SPANS["reading"](run, METRIC["name"])
