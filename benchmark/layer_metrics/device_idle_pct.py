"""1 - (union of device-operation intervals / traced span), the span being one
whole epoch period INCLUDING its tail: the first step program of one epoch
to the first of the next. Mean over the cell's chips.
"""

METRIC = {
    "name": "device_idle_pct",
    "unit": "%",
    "better": "lower",
    "source": "device_trace",
    "layer": "device (TPU v5e)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    trace = run["trace"]
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
