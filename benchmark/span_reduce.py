"""Reduction of the program's own spans (``telemetry.span_records()``: the
``telemetry.phase()`` records ``FeedForward.fit`` keeps in memory, stamped
with ``time.perf_counter`` like the runner's epoch rows) to the numbers the
``program_span`` per-layer metrics read. Kept with the benchmark so that
every PR computes them the same way; ``tests/benchmark`` pins it on
hand-written records.

A traced epoch is found by the clock: the runner stamps ``entry`` and
``exit`` inside ``epoch_end_callback``, which ``fit`` calls inside a
``fit.epoch.callback`` span, so measured epoch ``k`` lies between the
callback span around ``rows[k]`` and the one around ``rows[k + 1]``, and
its spans are those that carry the later callback's ``epoch``.

A program without the spans (``telemetry`` has no ``span_records``) reads
as ``None`` everywhere, and so does a metric whose records the ring has
dropped: it drops the oldest first, so an epoch whose earlier callback is
still there is whole, while any drop may have taken the set-up spans.
"""

from __future__ import annotations

import statistics

CONTAINERS = ("fit.start", "fit.epoch", "fit.step")   # they enclose the rest
NAMES = ("write_back_ms", "epoch_tail_host_ms", "epoch_tail_unnamed_ms",
         "host_step_ms_p10", "feed_wait_ms_per_step", "init_params_s",
         "fit_start_s")


def program_records():
    """``(records, dropped)`` from the program in this process, or ``None``
    if it keeps no span records."""
    from mxnet_tpu import telemetry      # the runner has imported it

    if not hasattr(telemetry, "span_records"):
        return None
    return telemetry.span_records(), telemetry.spans_dropped()


def _seconds(record):
    return record["end"] - record["start"]


def _covered(records, lo, hi):
    """Seconds of ``[lo, hi]`` that the records cover."""
    total, cur = 0.0, lo
    for s, e in sorted((r["start"], r["end"]) for r in records):
        s, e = max(s, cur), min(e, hi)
        if e > s:
            total += e - s
            cur = e
    return total


def traced_epochs(records, rows, traced):
    """For each traced epoch (0-based among the measured ones) the callback
    span before it, the one that ends it and the main-thread records that
    carry its ``epoch``; ``None`` if a callback span is not in the ring."""
    callbacks = [r for r in records if r["name"] == "fit.epoch.callback"]

    def around(row):
        for c in callbacks:
            if c["start"] <= row["entry"] and row["exit"] <= c["end"]:
                return c
        return None

    out = []
    for k in traced:
        before, after = around(rows[k]), around(rows[k + 1])
        if before is None or after is None:
            return None
        thread = [r for r in records if r["thread"] == after["thread"]]
        out.append({"before": before, "after": after, "thread": thread,
                    "own": [r for r in thread
                            if r["epoch"] == after["epoch"]]})
    return out


def _tail(epoch):
    """``(seconds, unnamed seconds)`` of one epoch's tail as the host sees
    it: the callback's end to the end of the first dispatch, and the
    drain's end (the device has finished the last step) to the next
    callback's start."""
    own = epoch["own"]
    dispatches = [r for r in own if r["name"] == "fit.dispatch"]
    drains = [r for r in own if r["name"] == "fit.epoch.drain"]
    if not dispatches or not drains:
        return None
    first = min(dispatches, key=lambda r: r["start"])
    pieces = [(epoch["before"]["end"], first["end"]),
              (drains[-1]["end"], epoch["after"]["start"])]
    named = [r for r in epoch["thread"] if r["name"] not in CONTAINERS]
    seconds = sum(hi - lo for lo, hi in pieces)
    return seconds, seconds - sum(_covered(named, lo, hi)
                                  for lo, hi in pieces)


def reduce(records, dropped, rows, traced):
    """Every ``NAMES`` reading, ``None`` where it cannot be read."""
    out = dict.fromkeys(NAMES)
    if not dropped:
        inits = [r for r in records if r["name"] == "setup.init_params"]
        starts = [r for r in records if r["name"] == "fit.start"]
        if inits:
            out["init_params_s"] = sum(_seconds(r) for r in inits)
        if starts:
            out["fit_start_s"] = _seconds(starts[-1])
    epochs = traced_epochs(records, rows, traced)
    if not epochs:
        return out
    write_backs, steps, waits, tails = [], [], [], []
    for epoch in epochs:
        by_name = {}
        for r in epoch["own"]:
            by_name.setdefault(r["name"], []).append(_seconds(r))
        write_backs += by_name.get("fit.epoch.write_back", [])[-1:]
        own_steps = by_name.get("fit.step", [])
        steps += own_steps
        if own_steps:
            waits.append(sum(by_name.get("fit.feed_wait", []))
                         / len(own_steps))
        tail = _tail(epoch)
        if tail is not None:
            tails.append(tail)
    if len(write_backs) == len(epochs):
        out["write_back_ms"] = 1e3 * statistics.median(write_backs)
    if len(tails) == len(epochs):
        out["epoch_tail_host_ms"] = 1e3 * statistics.median(
            t[0] for t in tails)
        out["epoch_tail_unnamed_ms"] = 1e3 * statistics.median(
            t[1] for t in tails)
    if len(steps) >= 2:
        out["host_step_ms_p10"] = 1e3 * statistics.quantiles(steps, n=10)[0]
    if len(waits) == len(epochs):
        out["feed_wait_ms_per_step"] = 1e3 * statistics.median(waits)
    return out


def reading(run, name):
    """The reading ``name`` of this run, reduced once and kept on ``run``."""
    if "span_readings" not in run:
        found = program_records()
        run["span_readings"] = dict.fromkeys(NAMES) if found is None \
            else reduce(*found, run["rows"], run["traced_epochs"])
    return run["span_readings"][name]
