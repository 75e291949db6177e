"""The runner's first statement to the first measured epoch's start: imports,
backend, model, data, weights, ``precompile`` and ``fit``'s start-up with
the untimed warm-up epoch (tail included).
"""

METRIC = {
    "name": "setup_s",
    "unit": "s",
    "better": "lower",
    "source": "host_clock",
}


def read(run):
    return run["setup_s"]
