"""Time per step in which an all-reduce, reduce-scatter, all-gather,
all-to-all or collective-permute ran on one chip (chip 0's line).
"""

METRIC = {
    "name": "collective_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "collectives (comm/, kvstore.py)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    trace = run["trace"]
    if not trace or not trace["collective_ops"]:
        return None
    return trace["collective_ms_per_step"]
