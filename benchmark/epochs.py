"""The window of a run: whole epochs, each read on the host clock.

A run's throughput is ALL the samples of the window's whole epochs over
ALL its seconds, from the first measured epoch's start (the warm-up
callback's return) to the last one's end (its callback's entry): a stall
anywhere in the window costs what it cost. Nothing is divided by the
nominal ``--seconds`` and no partial epoch is counted. The per-epoch
readings are printed, and read by per-layer metrics, so that a slow run
can be told apart afterwards: one slow epoch is a stall on the host, all
epochs slow is the machine.

An epoch's seconds run from the previous ``epoch_end_callback``'s return to
this one's entry. ``fit`` calls the callback after its own
``block_until_ready``, metric pull and parameter write-back, so the epoch
tail is inside the reading. What the benchmark itself does inside a
callback between two measured epochs (two stamps and a probe, some tens of
microseconds) is in the window's seconds and in no epoch's.
"""

from __future__ import annotations


class StopFit(Exception):
    """Raised from the epoch callback to end ``fit`` at an epoch boundary."""


class EpochClock:
    """``epoch_end_callback`` of the harness.

    Epoch 0 is the untimed warm-up (its tail included). Measured epochs
    follow until ``seconds`` have passed since the warm-up callback
    returned and at least ``min_epochs`` are in; then ``StopFit``.
    ``probe()`` is called at every callback entry and returns a dict kept
    with the epoch (loss, compile counters, steps dispatched).
    ``hooks`` maps a callback's ordinal (0 = the warm-up's, 1 = the first
    measured epoch's) to a callable run inside that callback, between the
    two stamps. ``span`` opens a trace span over every epoch, callback
    return to callback entry.
    """

    def __init__(self, clock, seconds, min_epochs, probe, hooks=None,
                 span=None):
        self.clock = clock
        self.seconds = float(seconds)
        self.min_epochs = int(min_epochs)
        self.probe = probe
        self.hooks = dict(hooks or {})
        self.span = span        # name -> context manager (a trace span)
        self._open = None
        self.rows = []          # one per callback, warm-up first

    def close(self):
        """End the open epoch span, if any."""
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def __call__(self, epoch, symbol=None, arg_params=None, aux_params=None):
        entry = self.clock()
        self.close()
        row = {"epoch": len(self.rows), "entry": entry, **self.probe()}
        self.rows.append(row)
        hook = self.hooks.get(row["epoch"])
        if hook is not None:
            hook()
        measured = len(self.rows) - 1
        if measured >= self.min_epochs and \
                entry - self.rows[0]["exit"] >= self.seconds:
            row["exit"] = self.clock()
            raise StopFit
        if self.span is not None:
            self._open = self.span("fit.epoch")
            self._open.__enter__()
        row["exit"] = self.clock()


def epoch_seconds(rows):
    """Seconds of each measured epoch: entry of its callback minus the
    exit of the one before. ``rows[0]`` is the warm-up."""
    return [rows[i]["entry"] - rows[i - 1]["exit"]
            for i in range(1, len(rows))]


def epoch_rates(rows, samples_per_epoch, chips):
    """samples/s/chip of each measured epoch."""
    return [samples_per_epoch / s / chips for s in epoch_seconds(rows)]


def window_seconds(rows):
    """The whole window: the warm-up callback's return to the last measured
    epoch's callback entry."""
    return rows[-1]["entry"] - rows[0]["exit"]
