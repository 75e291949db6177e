"""All the samples of the window's whole epochs / all the window's host-clock
seconds (first measured epoch's start to last measured epoch's end) / chips.
A stall anywhere in the window costs what it cost. See ``epochs.py`` for
where the window starts and ends; the median of the per-epoch rates stands
beside it as the per-layer metric ``epoch_rate_median``.
"""

METRIC = {
    "name": "samples_per_s_per_chip",
    "unit": "samples/s/chip",
    "better": "higher",
    "source": "host_clock",
}


def read(run):
    samples = run["samples_per_epoch"] * len(run["epoch_seconds"])
    return samples / run["window_seconds"] / run["chips"]
