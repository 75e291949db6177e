"""A traced epoch's host-clock seconds minus the span from its first step
program's start to its last step program's end on the device: what the
epoch costs outside its steps (metric pull, parameter write-back, feed
restart). Median over the traced epochs.
"""

import statistics

METRIC = {
    "name": "epoch_tail_ms",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "entry / epoch loop (model.py fit)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    trace = run["trace"]
    if not trace or not trace["epoch_device_span_s"]:
        return None
    host = [run["epoch_seconds"][i] for i in run["traced_epochs"]]
    tails = [h - d for h, d in zip(host, trace["epoch_device_span_s"])]
    return 1e3 * statistics.median(tails)
