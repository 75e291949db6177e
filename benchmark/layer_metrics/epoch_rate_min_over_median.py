"""The slowest epoch's rate over the median epoch's, in the traced run's own
window: the stall a user of the trainer would feel, which with only
epoch-granular honest sync points is a maximum over a handful of readings
and so decides no PR.
"""

import statistics

METRIC = {
    "name": "epoch_rate_min_over_median",
    "unit": "%",
    "better": "higher",
    "source": "host_clock",
    "layer": "entry / epoch loop (model.py fit)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    rates = run["epoch_rates"]
    return 100.0 * min(rates) / statistics.median(rates)
