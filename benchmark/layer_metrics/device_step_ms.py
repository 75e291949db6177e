"""Median duration of the train-step program on the device (the slowest
chip's median when there are several).
"""

METRIC = {
    "name": "device_step_ms",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    trace = run["trace"]
    return trace["device_step_ms_p50"] if trace else None
