"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read. Kept with the benchmark so that every PR computes
them the same way; ``tests/benchmark`` pins its output on a recorded trace.

What a TPU trace holds (looked at by hand, PR 23, one v5e): one plane per
chip named ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event
per program execution, named ``jit_step(<run id>)``), ``XLA Ops`` (one
event per HLO operation, about 4,150 a ResNet-50 step, named by the whole
HLO instruction ``%fusion.21 = (...) fusion(...)``: cut here to
``fusion.21``) and ``Async XLA Ops`` (copies and slices in flight beside
them), and a plane ``/host:CPU`` with one line per host thread, on which
the benchmark's own ``jax.profiler.TraceAnnotation`` spans (names starting
``bench.``) and the program's (``telemetry.phase()``: ``mx.``) appear. All
start times are nanoseconds on one clock. Besides the train step, ``fit``
runs three tiny programs a step (``jit_convert_element_type``,
``jit__threefry_split``, ``jit__unstack``). The events carry no scope: an
instruction's is read from the program's HLO text (``scopes.py``).

The train-step program is not looked up by name: it is the module that
took most device time in the trace, which in a traced training window it
is by two orders of magnitude.
"""

from __future__ import annotations

import bisect
import gzip
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = ("bench.", "mx.")    # the harness's spans, the program's
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)")


def load(path):
    """Read an ``.xplane.pb``, or a gzipped one (see :func:`events_of`)."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return events_of(ProfileData.from_serialized_xspace(f.read()))
    return events_of(ProfileData.from_file(str(path)))


def _short(name):
    """``%fusion.21 = (f32[256]...) fusion(...)`` -> ``fusion.21``."""
    return name.split(" = ", 1)[0].lstrip("%")


def events_of(data):
    """A ``jax.profiler.ProfileData`` as plain lists:
    ``{"devices": {n: {"modules": [...], "ops": [...], "async": [...]}},
    "spans": [...]}``, every event a ``(name, start_ns, duration_ns)`` tuple
    sorted by start."""
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            rows = {"modules": [], "ops": [], "async": []}
            for line in plane.lines:
                key = {MODULES_LINE: "modules", OPS_LINE: "ops",
                       ASYNC_LINE: "async"}.get(line.name)
                if key is not None:
                    rows[key] = sorted(
                        ((_short(e.name), float(e.start_ns),
                          float(e.duration_ns)) for e in line.events),
                        key=lambda e: e[1])
            devices[int(m.group(1))] = rows
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    spans.sort(key=lambda e: e[1])
    return {"devices": devices, "spans": spans}


def _base(name):
    """``jit_step(1234)`` -> ``jit_step``: one program, whatever its run id."""
    return name.split("(")[0]


def union_ns(intervals):
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _clip(events, lo, hi):
    """``(start, end)`` of the events, cut to ``[lo, hi]``."""
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def _gaps(intervals, lo, hi):
    """Idle gaps ``(start, end)`` inside ``[lo, hi]`` not covered."""
    gaps, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def _name_gap(gap, spans, boundaries):
    """What the host was doing in an idle gap, as far as the kept spans
    can say: the shortest span that covers the gap's middle, without its
    prefix, then whether the gap straddles an epoch boundary."""
    mid = (gap[0] + gap[1]) / 2
    covering = [(d, n) for n, s, d in spans if s <= mid <= s + d]
    name = min(covering)[1].split(".", 1)[1] if covering else "untraced"
    where = "epoch_tail" if any(gap[0] <= b <= gap[1] for b in boundaries) \
        else "between_steps"
    return f"{name}/{where}"


def reduce_device(rows, steps_per_epoch, spans=()):
    """One chip's numbers. ``None`` if the chip ran no program."""
    modules, ops = rows["modules"], rows["ops"]
    if not modules:
        return None
    total = {}
    for name, _, d in modules:
        total[_base(name)] = total.get(_base(name), 0.0) + d
    program = max(total, key=total.get)
    steps = [(s, s + d) for name, s, d in modules if _base(name) == program]
    spe = int(steps_per_epoch)
    epochs = [steps[i:i + spe] for i in range(0, len(steps) - spe + 1, spe)]
    gaps = [b[0] - a[1] for a, b in zip(steps, steps[1:])]
    out = {
        "program": program,
        "steps": len(steps),
        "step_ms": [(e - s) / 1e6 for s, e in steps],
        "gap_ms": [g / 1e6 for g in gaps],
        "epoch_device_span_s": [(ep[-1][1] - ep[0][0]) / 1e9 for ep in epochs],
    }
    # the traced span: one whole epoch period, tail included — the first
    # step of the first traced epoch to the first step of the next
    if len(steps) > spe:
        lo, hi = steps[0][0], steps[spe][0]
    else:
        lo, hi = steps[0][0], steps[-1][1]
    n_steps = min(len(steps), spe)
    busy = _clip(ops if ops else modules, lo, hi)
    out["window_s"] = (hi - lo) / 1e9
    out["busy_s"] = union_ns(busy) / 1e9
    boundaries = [ep[-1][1] + 1 for ep in epochs]
    idle = sorted(_gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:10]
    out["idle_gaps"] = [[_name_gap(g, spans, boundaries), (g[1] - g[0]) / 1e9]
                        for g in idle]
    # every instruction's seconds in the traced span, longest first
    # (``top_ops`` is its head), the steps that span holds, and the same
    # for the instructions that ran inside an execution of the train
    # program: an instruction's name is unique in its program only, so a
    # join with that program's HLO text takes these (``scopes.py``)
    per_op, own = {}, {}
    starts = [s for s, _ in steps]
    for name, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            per_op[name] = per_op.get(name, 0.0) + (b - a)
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < steps[i][1]:
                own[name] = own.get(name, 0.0) + (b - a)

    def longest_first(seconds):
        return {n: t / 1e9 for n, t in
                sorted(seconds.items(), key=lambda kv: -kv[1])}

    out["op_seconds"] = longest_first(per_op)
    out["program_op_seconds"] = longest_first(own)
    out["span_steps"] = n_steps
    out["top_ops"] = [[n, t] for n, t in
                      list(out["op_seconds"].items())[:10]]
    is_coll = [bool(COLLECTIVE.match(e[0])) for e in ops]
    coll = _clip([e for e, c in zip(ops, is_coll) if c]
                 + [e for e in rows.get("async", ())
                    if COLLECTIVE.match(e[0])], lo, hi)
    other = _clip([e for e, c in zip(ops, is_coll) if not c], lo, hi)
    coll_ns = union_ns(coll)
    hidden_ns = coll_ns + union_ns(other) - union_ns(coll + other)
    out["collective_ms_per_step"] = coll_ns / 1e6 / n_steps
    out["collective_exposed_ms_per_step"] = (coll_ns - hidden_ns) / 1e6 / n_steps
    out["collective_ops"] = len(coll)
    return out


def reduce(trace, steps_per_epoch):
    """All chips: per-chip dicts under ``per_device`` and the numbers the
    metrics read, the slowest-reading chip's for times and the mean over
    chips for busy seconds. ``None`` if no chip ran a program."""
    per = {n: reduce_device(rows, steps_per_epoch, trace["spans"])
           for n, rows in sorted(trace["devices"].items())}
    per = {n: r for n, r in per.items() if r is not None}
    if not per:
        return None
    first = per[min(per)]
    chips = len(per)
    return {
        "per_device": per,
        "chips": chips,
        "program": first["program"],
        "steps": first["steps"],
        "device_step_ms_p50": max(statistics.median(r["step_ms"])
                                  for r in per.values()),
        "step_gap_ms_p50": max(statistics.median(r["gap_ms"])
                               for r in per.values()) if first["gap_ms"]
        else None,
        "epoch_device_span_s": first["epoch_device_span_s"],
        "window_s": sum(r["window_s"] for r in per.values()) / chips,
        "busy_s": sum(r["busy_s"] for r in per.values()) / chips,
        "top_ops": first["top_ops"],
        "op_seconds": first["op_seconds"],
        "program_op_seconds": first["program_op_seconds"],
        "span_steps": first["span_steps"],
        "idle_gaps": first["idle_gaps"],
        "collective_ms_per_step": first["collective_ms_per_step"],
        "collective_exposed_ms_per_step":
            first["collective_exposed_ms_per_step"],
        "collective_ops": first["collective_ops"],
    }
