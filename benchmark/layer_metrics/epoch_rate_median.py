"""MEDIAN over the traced run's whole epochs of (samples in the epoch /
host-clock seconds of that epoch) / chips: the steadier statistic beside the
end-to-end rate, which is all samples over all seconds. One stalled epoch
does not move it, so the two apart say "a stall", the two together "every
epoch".
"""

import statistics

METRIC = {
    "name": "epoch_rate_median",
    "unit": "samples/s/chip",
    "better": "higher",
    "source": "host_clock",
    "layer": "entry / epoch loop (model.py fit)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return statistics.median(run["epoch_rates"])
