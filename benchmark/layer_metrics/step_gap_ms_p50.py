"""Median gap on the device between one train-step program's end and the
next one's start: the host's dispatch keeping up, or not.
"""

METRIC = {
    "name": "step_gap_ms_p50",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "entry / epoch loop (model.py fit)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    trace = run["trace"]
    return trace["step_gap_ms_p50"] if trace else None
