"""The part of ``collective_ms_per_step`` during which no other operation ran
on that chip: what the collectives add to the step.
"""

METRIC = {
    "name": "collective_exposed_ms_per_step",
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
    return trace["collective_exposed_ms_per_step"]
