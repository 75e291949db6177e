"""Reduction of the program's set-up spans to the readings of the step
builder and compile management and of set-up: the train step's three
compile stages (``setup.compile.trace``, ``.lower``, ``.backend``, spans
``TrackedJit.precompile`` opens), every other XLA compile
(``compile.backend``, one record the compile registry leaves), and the part
of ``setup.precompile`` that none of its children names. Kept with the
benchmark, beside ``span_reduce.py`` whose ``program_records`` it reads;
``tests/benchmark`` pins it on hand-written records.

Set-up ends where ``setup_s`` ends it, at the warm-up epoch callback's
return (``run["rows"][0]["exit"]``): a record that ends later (the
reference's program, the HLO text, a compile inside the window) is not
set-up's. A program without ``setup.precompile`` (one from before these
spans) reads as ``None`` everywhere, and so does a run whose ring has
dropped a record.
"""

from __future__ import annotations

import os
import runpy

SPANS = runpy.run_path(os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "span_reduce.py"))

TRAIN = "train_step:"                   # the train programs' labels
CONTAINER = "setup.precompile"
STAGES = {"train_step_trace_s": "setup.compile.trace",
          "train_step_lower_s": "setup.compile.lower",
          "train_step_backend_s": "setup.compile.backend"}
NAMES = tuple(STAGES) + ("setup_other_compile_s", "precompile_unnamed_s")


def _seconds(record):
    return record["end"] - record["start"]


def _train(record):
    return str(record["attrs"].get("label", "")).startswith(TRAIN)


def _unnamed(container, records):
    """Seconds of ``container`` that none of its children covers: the
    records whose parent it is on its thread, and the ``setup.compile``
    spans that parallel precompile opens on threads of their own."""
    children = [r for r in records if r is not container and (
        (r["parent"] == CONTAINER and r["thread"] == container["thread"])
        or (r["name"] == "setup.compile" and r["parent"] is None
            and r["thread"] != container["thread"]))]
    return _seconds(container) - SPANS["_covered"](
        children, container["start"], container["end"])


def reduce(records, dropped, setup_end):
    """Every ``NAMES`` reading of the records that ended by ``setup_end``,
    ``None`` where it cannot be read."""
    out = dict.fromkeys(NAMES)
    if dropped:
        return out
    records = [r for r in records if r["end"] <= setup_end]
    containers = [r for r in records if r["name"] == CONTAINER]
    if not containers:
        return out
    for name, stage in STAGES.items():
        spans = [r for r in records if r["name"] == stage and _train(r)]
        if spans:
            out[name] = sum(_seconds(r) for r in spans)
    out["setup_other_compile_s"] = sum(
        _seconds(r) for r in records
        if r["name"] == "compile.backend" and not _train(r))
    out["precompile_unnamed_s"] = sum(_unnamed(c, records)
                                      for c in containers)
    return out


def reading(run, name):
    """The reading ``name`` of this run, reduced once and kept on ``run``."""
    if "setup_span_readings" not in run:
        found = SPANS["program_records"]()
        run["setup_span_readings"] = dict.fromkeys(NAMES) if found is None \
            else reduce(*found, run["rows"][0]["exit"])
    return run["setup_span_readings"][name]
