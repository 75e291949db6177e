"""``program_step_bytes`` of the train program's memory plan (temp + output -
aliased bytes, per chip), in MB of 10**6 bytes: what a step needs beyond
the resident state.
"""

METRIC = {
    "name": "plan_mb",
    "unit": "MB",
    "better": "lower",
    "source": "program_counter",
    "layer": "memory (telemetry/memory.py)",
    "moves": "peak_hbm_mb",
}


def read(run):
    return run["plan_bytes"] / 1e6 if run["plan_bytes"] else None
