"""Finding a cell's files by the names ``BENCHMARK.json`` gives.

Whatever belongs to one configuration, one traffic mix or one metric sits
in a file of its own: a later PR adds a cell by adding files and entries,
never by editing the runner.

  configuration  the ``file`` of its ``configs`` entry (JSON), with its
                 plain reference (and, if the model needs code, its
                 builder) beside it
  traffic mix    ``traffic/<traffic>.json``; its ``kind`` names the feeder
                 ``feeds/<kind>.py`` (``make(...)``: an object with
                 ``iter``, the DataIter ``fit`` gets, whose ``ring[0]`` is
                 a (data, label) batch as it is handed over,
                 ``steps_per_epoch``, ``batch_rows``, ``check_rows(n)``)
  metric         ``end_to_end/<name>.py`` or ``layer_metrics/<name>.py``
                 (``METRIC`` and ``read(run)``). The cells a metric is
                 read in, its ``workloads`` list, live in
                 ``BENCHMARK.json`` only: a reader's ``METRIC`` never has
                 the key, so a later PR appends its cell to the list there
                 and the reader takes that cell's sizes from
                 ``run["config"]``
  operator       ``walkers/<Operator>.py`` (``layers(node, in_shapes,
                 out_shapes)``): what a node of that operator adds to the
                 FLOP recipe, found by ``walk.py``
  peaks          ``peaks.json``, keyed by ``device_kind``
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchmarkError(Exception):
    """The benchmark's own files are inconsistent or a name is unknown."""


def read_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_benchmark(root=ROOT):
    return read_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(bench, workload, root=ROOT, here=HERE):
    """The cell named ``workload``: its entry, its configuration (the
    ``configs`` entry, the file's path and content) and its traffic mix."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"unknown workload {workload!r}; BENCHMARK.json "
                             f"has {sorted(cells)}")
    cell = cells[workload]
    entries = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in entries:
        raise BenchmarkError(f"workload {workload!r} names configuration "
                             f"{cell['config']!r}, which configs lacks")
    entry = entries[cell["config"]]
    config_path = os.path.join(root, entry["file"])
    return {
        "cell": cell,
        "config_entry": entry,
        "config_path": config_path,
        "config": read_json(config_path),
        "traffic": read_json(os.path.join(here, "traffic",
                                          cell["traffic"] + ".json")),
    }


def metrics_for(bench, group, workload):
    """The ``group`` (``end_to_end`` / ``per_layer``) entries this cell
    reports: those with no ``workloads`` key, or that list it."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def load_file_module(path, name):
    if not os.path.isfile(path):
        raise BenchmarkError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_feeder(kind, here=HERE):
    return load_file_module(os.path.join(here, "feeds", kind + ".py"),
                            "bench_feed_" + kind)


def load_metric(group_dir, name, here=HERE):
    """``group_dir`` is ``end_to_end`` or ``layer_metrics``."""
    safe = re.sub(r"[^A-Za-z0-9_]", "_", name)
    return load_file_module(os.path.join(here, group_dir, name + ".py"),
                            f"bench_{group_dir}_{safe}")


def build_symbol(builder, beside):
    """The model's symbol from a configuration's ``builder``: either
    ``{"import": "package.module:callable", "kwargs": {...}}`` or, for a
    model that needs code of its own, ``{"file": "<sibling>.py", "call":
    "<function>", "kwargs": {...}}`` with the file in ``beside``, the
    directory of the configuration's file."""
    if "file" in builder:
        fn = getattr(load_file_module(
            os.path.join(beside, builder["file"]),
            "bench_builder_" + re.sub(r"[^A-Za-z0-9_]", "_", builder["file"])),
            builder["call"])
    else:
        module, _, attr = builder["import"].partition(":")
        fn = getattr(importlib.import_module(module), attr)
    kwargs = {k: tuple(v) if isinstance(v, list) else v
              for k, v in builder.get("kwargs", {}).items()}
    return fn(**kwargs)


def peak_for(device_kind, here=HERE):
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = read_json(os.path.join(here, "peaks.json"))["peaks"]
    if device_kind not in table:
        raise BenchmarkError(f"no published peak for device kind "
                             f"{device_kind!r} in peaks.json")
    return table[device_kind]
