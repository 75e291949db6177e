"""Device-time profiler: measured per-layer/per-kernel attribution (ISSUE 15).

Every performance number the stack reported before this module — MFU,
Goodput, the roofline rows, the jaxpr-audit FLOP tables — was *modeled*:
the timeline knows a step spent N ms in the ``device`` phase but nothing
about where inside the XLA program that time went. This module closes the
modeled-vs-measured gap end to end (TensorFlow's op-level device profiling
stance, arXiv:1605.08695; the reference's operator profiler,
arXiv:1512.01274):

  **provenance in** — the executor emits every symbol op under
  ``jax.named_scope(<layer>/<op>)`` (executor.exec_node), the fused train
  step scopes its non-graph stages (``comm``/``optimizer``/``metric``/
  ``guards``/``health``/``loss``), and Pallas kernels already carry
  ``name=`` from the kernel registry — so XLA op *metadata* names the
  source layer of every instruction. Scopes are trace-time metadata only:
  the compiled program, its cache keys, and the armed zero-recompile
  invariant are untouched.

  **capture** — ``fit(profile=...)`` / ``predict(profile=...)`` / env
  ``MXNET_TPU_PROFILE`` arm a bounded K-step capture window through
  ``jax.profiler`` (:func:`start_capture`/:func:`stop_capture`/
  :func:`capture` — the ONE sanctioned entry to the jax profiler; mxlint
  MX314 polices strays). Windows open only after warmup and never in a
  compile-polluted step, and their wall time is priced as a ``profile``
  badput bucket so Goodput stays honest.

  **attribution** — :func:`parse_trace_dir` digests the emitted profile
  (``*.trace.json.gz``; backend-agnostic — the CPU rig's Eigen/TfrtCpu
  lanes and a real TPU's "XLA Ops" lanes both carry per-instruction
  events), and :func:`build_report` joins device events back to layers
  through the HLO metadata map (instruction -> ``op_name`` -> named
  scope). The report carries an attribution **coverage ratio** and an
  explicit ``unattributed`` row — measured time that cannot be named is
  reported, never hidden.

  **measured roofline** — measured per-primitive seconds join the
  jaxpr-audit FLOP/byte models (kernel-registry rows included) into
  roofline rows stamped ``source: "measured"``: achieved FLOP/s,
  %-of-peak, and a compute- vs bandwidth-bound classification per op.
  The same join gives MFU a *measured* numerator to reconcile against
  the modeled one (``mfu`` block of the report).

Surface: ``profile`` events in the JSONL schema, per-layer ``profile_*``
hub gauges, ``python -m mxnet_tpu.telemetry profile run.jsonl`` hotspot
tables, per-op rows in the ``telemetry diff`` CI perf gate, and the last
capture summary embedded in flight-recorder dumps.
"""

from __future__ import annotations

import collections
import contextlib
import glob
import gzip
import json
import logging
import os
import re
import tempfile
import time

from ..analysis.lockwatch import named_lock
from ..base import ENV_OFF_VALUES
from .hub import hub as _hub

__all__ = ["ProfileConfig", "ProfileSession", "ProfileReport",
           "start_capture", "stop_capture", "capture", "capture_active",
           "parse_trace_dir", "hlo_op_metadata", "attribute_op_name",
           "build_report", "measured_peak_bandwidth",
           "last_capture_summary", "CATEGORY_SCOPES", "WRAPPER_SEGMENTS"]

# scope segments the train step emits for its non-graph stages; attribution
# treats them as pseudo-layers so optimizer/comm/metric time is named, not
# lost to the unattributed row
CATEGORY_SCOPES = frozenset({"optimizer", "comm", "metric", "guards",
                             "health", "loss"})

# transform/partitioning wrapper segments jax inserts around user scopes in
# op_name metadata ("jit(step)/jit(main)/transpose(jvp(f))/fc1/...") —
# never a layer. Parenthesized segments are skipped structurally.
WRAPPER_SEGMENTS = frozenset({
    "jit", "pjit", "jvp", "vjp", "transpose", "vmap", "pmap", "scan",
    "while", "body", "cond", "branch", "checkpoint", "remat", "shmap",
    "shmap_body", "shard_map", "custom_jvp", "custom_vjp",
    "custom_vjp_call", "main",
})

# HLO control-flow wrapper instructions whose duration covers the inner
# instructions that also appear in the trace — counting both would
# double-book the window (the CPU backend outlines thread-parallel regions
# under `call`; while/conditional wrap their bodies the same way)
_WRAPPER_INSTRS = ("call", "while", "conditional", "async-start",
                   "async-done")

_OFF = ENV_OFF_VALUES
_ON_VALUES = ("1", "on", "true", "yes")

# per-process window counter: each ProfileSession window captures into its
# own subdirectory of an explicit cfg.log_dir (see ProfileSession._begin)
import itertools as _itertools

_WINDOW_SEQ = _itertools.count()


class ProfileConfig:
    """What ``fit(profile=...)`` / ``predict(profile=...)`` turns on.

    ``steps``: capture-window length in steps. ``warmup``: observed steps
    to skip before the window may open (and the window additionally waits
    for a compile-quiet step — never capture a compile). ``log_dir``:
    where the raw trace lands (None = a kept temp dir, so the full trace
    can still be opened in the profiler UI). ``top_k``: hotspot-table
    length. ``gauges``: export per-layer ``profile_*`` gauges."""

    def __init__(self, steps=6, warmup=2, log_dir=None, top_k=12,
                 gauges=True):
        self.steps = max(int(steps), 1)
        self.warmup = max(int(warmup), 0)
        self.log_dir = log_dir
        self.top_k = max(int(top_k), 1)
        self.gauges = bool(gauges)

    def __repr__(self):
        return (f"ProfileConfig(steps={self.steps}, warmup={self.warmup}, "
                f"log_dir={self.log_dir!r}, top_k={self.top_k})")

    @classmethod
    def resolve(cls, value):
        """Normalize the ``profile`` argument: None -> env gate
        ``MXNET_TPU_PROFILE`` (unset/falsy = off; an integer = window
        steps; any other value = defaults), True -> defaults, int ->
        window steps, ProfileConfig -> itself."""
        if value is None:
            raw = os.environ.get("MXNET_TPU_PROFILE", "").strip()
            if not raw or raw.lower() in _OFF:
                return None
            value = int(raw) if raw.isdigit() and raw.lower() not in \
                _ON_VALUES else True
        if value is False:
            return None
        if value is True:
            return cls()
        if isinstance(value, cls):
            return value
        if isinstance(value, int):
            # 0 means off, like the env gate's MXNET_TPU_PROFILE=0 — a
            # computed "no window" must not silently become a 1-step one
            return cls(steps=value) if value > 0 else None
        raise ValueError(
            f"profile must be bool/None/int/ProfileConfig, got {type(value)}")


# -- capture: the one sanctioned doorway to jax.profiler -----------------------
# jax's profiler is process-global (one trace at a time); every capture in
# the stack goes through here so (a) concurrent windows fail soft instead
# of crashing the run, (b) every capture is a hub event a JSONL sink sees,
# and (c) mxlint MX314 can police strays syntactically.

_CAPTURE_LOCK = named_lock("telemetry.profiling.capture")
_CAPTURE = {"dir": None, "t0": None, "owner": None}
_LAST_SUMMARY = [None]  # most recent capture summary (flight-recorder page)


def capture_active():
    """The active capture's log dir, or None."""
    with _CAPTURE_LOCK:
        return _CAPTURE["dir"]


def start_capture(log_dir=None, owner="manual"):
    """Start a device-trace capture (``jax.profiler.start_trace``).

    Returns the log dir. Raises RuntimeError if a capture is already
    active — the caller decides whether that is fatal (the fit session
    skips its window instead)."""
    import jax

    log_dir = log_dir or tempfile.mkdtemp(prefix="mxtpu_profile_")
    with _CAPTURE_LOCK:
        if _CAPTURE["dir"] is not None:
            raise RuntimeError(
                f"a profile capture is already active "
                f"(owner={_CAPTURE['owner']!r}, dir={_CAPTURE['dir']!r})")
        jax.profiler.start_trace(log_dir)
        _CAPTURE.update(dir=log_dir, t0=time.perf_counter(), owner=owner)
    _hub().emit("profile", phase="start", owner=str(owner),
                log_dir=str(log_dir), steps=0, device_ms=0.0,
                coverage_pct=None)
    _hub().counter("profile_captures_total")
    return log_dir


def stop_capture():
    """Stop the active capture; returns ``(log_dir, wall_seconds)`` (or
    ``(None, 0.0)`` when none is active — a finally-guarded stop must be
    safe to call unconditionally)."""
    import jax

    with _CAPTURE_LOCK:
        if _CAPTURE["dir"] is None:
            return None, 0.0
        log_dir, t0 = _CAPTURE["dir"], _CAPTURE["t0"]
        owner = _CAPTURE["owner"]
        try:
            jax.profiler.stop_trace()
        finally:
            _CAPTURE.update(dir=None, t0=None, owner=None)
    seconds = time.perf_counter() - t0
    _hub().emit("profile", phase="capture", owner=str(owner),
                log_dir=str(log_dir), seconds=seconds, steps=0,
                device_ms=0.0, coverage_pct=None)
    _hub().gauge("profile_capture_seconds", seconds)
    return log_dir, seconds


@contextlib.contextmanager
def capture(log_dir=None, owner="manual"):
    """Context-managed capture window (finally-guarded stop — the shape
    mxlint MX314 asks of every caller)."""
    log_dir = start_capture(log_dir, owner=owner)
    try:
        yield log_dir
    finally:
        stop_capture()


# -- trace parsing (backend-agnostic) ------------------------------------------

def parse_trace_dir(log_dir, device_substr="", drop_wrappers=True):
    """Aggregate per-instruction device time from a captured trace dir.

    Reads every ``*.trace.json.gz`` under ``log_dir`` and keeps complete
    ("X") events that name an XLA instruction — either through the
    ``hlo_op``/``hlo_module`` event args (the CPU backend's Eigen /
    TfrtCpuClient lanes) or by landing on an "XLA Ops" lane (the TPU
    export, where the event name IS the instruction). With
    ``drop_wrappers`` (the attribution default), control-flow wrapper
    instructions (``call``/``while``/...) are dropped: their duration
    covers the inner instructions that also appear, and summing both
    would double-book the window. ``device_substr`` filters by process
    name (e.g. "TPU"). This is the ONE trace parser —
    ``utils.profiler.trace_op_stats`` is a rollup over it.

    Returns ``{(module, instr): {"us": total, "count": n}}``.
    """
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.trace.json.gz"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no trace.json.gz under {log_dir!r}")
    rows: dict = {}
    for path in files:
        with gzip.open(path, "rt") as f:
            data = json.load(f)
        events = data.get("traceEvents", [])
        procs = {e["pid"]: e["args"].get("name", "")
                 for e in events
                 if e.get("ph") == "M" and e.get("name") == "process_name"
                 and isinstance(e.get("args"), dict)}
        lanes = {(e["pid"], e["tid"]): e["args"].get("name", "")
                 for e in events
                 if e.get("ph") == "M" and e.get("name") == "thread_name"
                 and isinstance(e.get("args"), dict)}
        for e in events:
            if e.get("ph") != "X":
                continue
            if device_substr and device_substr not in \
                    procs.get(e.get("pid"), ""):
                continue
            args = e.get("args") or {}
            instr = args.get("hlo_op")
            module = args.get("hlo_module")
            if instr is None:
                lane = lanes.get((e.get("pid"), e.get("tid")), "")
                if "XLA Ops" not in lane:
                    continue
                instr = e.get("name", "")
                module = args.get("hlo_module", "")
            if drop_wrappers and instr.split(".")[0] in _WRAPPER_INSTRS:
                continue
            key = (str(module or "?"), str(instr))
            row = rows.get(key)
            if row is None:
                row = rows[key] = {"us": 0.0, "count": 0}
            row["us"] += float(e.get("dur", 0.0))
            row["count"] += 1
    return rows


_HLO_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)", re.MULTILINE)
_HLO_INSTR_RE = re.compile(
    r"%([\w.\-]+)\s*=[^\n]*?metadata=\{[^}]*?op_name=\"([^\"]+)\"")


def hlo_op_metadata(hlo_text):
    """``(module_name, {instruction: op_name})`` from compiled HLO text —
    the join key between trace events and named scopes. Instructions
    without ``op_name`` metadata are simply absent (they land in the
    report's ``unattributed`` row)."""
    m = _HLO_MODULE_RE.search(hlo_text)
    module = m.group(1) if m else "?"
    return module, dict(_HLO_INSTR_RE.findall(hlo_text))


def hlo_texts_from_tracked(tracked, *args, **kwargs):
    """Compiled-HLO text(s) for a TrackedJit's program(s).

    Prefers executables already AOT-registered (free); otherwise
    ``precompile``\\s for the given concrete/abstract args — accounted as
    a *precompile* in the program registry, so an armed RecompileTracker
    (which observes cache *misses*) stays green, and the executable then
    serves subsequent dispatches. Returns ``[]`` when the backend hides
    the text (attribution degrades to coverage 0, never raises)."""
    texts = []
    try:
        compiled_set = list(getattr(tracked, "_aot", {}).values())
        if not compiled_set and args:
            compiled_set = [tracked.precompile(*args, **kwargs)]
        for compiled in compiled_set:
            texts.append(compiled.as_text())
    except Exception as e:  # backend-dependent introspection
        logging.debug("profiling: HLO text unavailable: %s", e)
    return texts


# -- attribution ---------------------------------------------------------------

# transform applications in op_name metadata: jax nests the user scopes
# INSIDE the parens — "transpose(jvp(fc1/FullyConnected))/dot_general" —
# so wrappers unwrap (drop "name(" and ")") rather than drop wholesale
_TRANSFORM_OPEN_RE = re.compile(r"[\w.\-]+\(")


def _scope_segments(op_name):
    """The named-scope path of an op_name: transform applications
    unwrapped, wrapper segments dropped."""
    flat = _TRANSFORM_OPEN_RE.sub("", op_name).replace(")", "")
    return [seg for seg in flat.split("/")
            if seg and seg not in WRAPPER_SEGMENTS]


def attribute_op_name(op_name, layers, categories=CATEGORY_SCOPES):
    """``(layer-or-category, primitive)`` for one metadata op_name, or
    ``(None, primitive)`` when no segment names a known layer. The
    primitive is the trailing segment (the jax primitive the measured
    roofline joins on)."""
    segs = _scope_segments(op_name)
    prim = segs[-1] if segs else op_name
    for seg in segs:
        if seg in layers:
            return seg, prim
        if seg in categories:
            return seg, prim
    return None, prim


class ProfileReport:
    """One capture window, attributed. ``to_dict()`` is the JSONL/flight
    payload; the fit session publishes it as the ``profile`` summary
    event plus ``profile_*`` gauges."""

    def __init__(self, steps, window_seconds, total_us, attributed_us,
                 layers, ops, roofline, mfu, log_dir=None, epoch=None):
        self.steps = int(steps)
        self.window_seconds = float(window_seconds)
        self.total_us = float(total_us)
        self.attributed_us = float(attributed_us)
        self.layers = layers          # {layer: us}
        self.ops = ops                # hotspot rows, sorted by us desc
        self.roofline = roofline      # measured roofline rows
        self.mfu = mfu                # measured-vs-modeled reconciliation
        self.log_dir = log_dir
        self.epoch = epoch

    @property
    def coverage_pct(self):
        if not self.total_us:
            return 0.0
        return 100.0 * self.attributed_us / self.total_us

    @property
    def unattributed_us(self):
        return self.total_us - self.attributed_us

    def to_dict(self, top_k=None):
        top = self.ops[:top_k] if top_k else list(self.ops)
        return {
            "steps": self.steps,
            "window_seconds": self.window_seconds,
            "device_ms": self.total_us / 1e3,
            "attributed_ms": self.attributed_us / 1e3,
            "unattributed_ms": self.unattributed_us / 1e3,
            "coverage_pct": self.coverage_pct,
            "layers": {k: v / 1e3 for k, v in sorted(
                self.layers.items(), key=lambda kv: -kv[1])},
            "top": top,
            "roofline": list(self.roofline),
            "mfu": dict(self.mfu),
            "log_dir": self.log_dir,
            "epoch": self.epoch,
        }

    def table(self, top_k=10):
        """Human-readable hotspot table (the fit log / CLI rendering)."""
        lines = [f"device profile: {self.total_us / 1e3:.2f} ms over "
                 f"{self.steps} step(s), coverage "
                 f"{self.coverage_pct:.1f}% "
                 f"(unattributed {self.unattributed_us / 1e3:.2f} ms)"]
        for row in self.ops[:top_k]:
            lines.append(
                f"  {row['us'] / 1e3:9.3f} ms {row['pct']:5.1f}%  "
                f"{row['layer'] or '<unattributed>':<20s} {row['op']}")
        return "\n".join(lines)


def build_report(trace_rows, hlo_maps, layers, categories=None, steps=1,
                 window_seconds=0.0, audit_rows=None, flops_per_step=None,
                 num_devices=1, peak_flops=None, log_dir=None, epoch=None):
    """Join parsed trace rows to layers/kernels and the FLOP/byte models.

    ``trace_rows``: :func:`parse_trace_dir` output. ``hlo_maps``: list of
    ``{instruction: op_name}`` maps (from :func:`hlo_op_metadata`).
    ``layers``: known layer names (symbol node names + param layers).
    ``audit_rows``: jaxpr-audit per-primitive rows of the profiled
    program (``flops``/``bytes`` PER STEP) — the measured-roofline join;
    kernel-registry rows arrive as ``pallas::<name>`` primitives.
    ``flops_per_step``/``num_devices``/``peak_flops``: the MFU
    reconciliation inputs (aggregate peak)."""
    categories = set(categories if categories is not None
                     else CATEGORY_SCOPES)
    try:
        from ..ops.pallas import registry as kreg

        categories |= set(kreg.kernel_names())
    except Exception:
        pass
    merged = {}
    for m in hlo_maps:
        merged.update(m)

    total_us = attributed_us = 0.0
    layer_us: dict = collections.defaultdict(float)
    op_rows: dict = {}
    prim_us: dict = collections.defaultdict(float)
    for (module, instr), row in trace_rows.items():
        us = row["us"]
        total_us += us
        op_name = merged.get(instr)
        layer = prim = None
        if op_name is not None:
            layer, prim = attribute_op_name(op_name, layers, categories)
        if layer is None and op_name is None:
            # fusions carry their root's metadata; a bare instruction with
            # no map entry keeps its HLO opcode as the "primitive"
            prim = instr.split(".")[0]
        if layer is not None:
            attributed_us += us
            layer_us[layer] += us
        prim_us[prim] += us
        key = (layer, prim)
        orow = op_rows.get(key)
        if orow is None:
            orow = op_rows[key] = {"layer": layer, "op": prim, "us": 0.0,
                                   "count": 0, "program": module}
        orow["us"] += us
        orow["count"] += row["count"]

    ops = sorted(op_rows.values(), key=lambda r: -r["us"])
    for row in ops:
        row["pct"] = 100.0 * row["us"] / total_us if total_us else 0.0
        row["ms_per_step"] = row["us"] / 1e3 / max(steps, 1)

    roofline = _measured_roofline(prim_us, audit_rows, steps, num_devices,
                                  peak_flops)
    mfu = _reconcile_mfu(total_us, steps, num_devices, flops_per_step,
                         peak_flops, window_seconds)
    return ProfileReport(steps, window_seconds, total_us, attributed_us,
                         dict(layer_us), ops, roofline, mfu,
                         log_dir=log_dir, epoch=epoch)


def _measured_roofline(prim_us, audit_rows, steps, num_devices, peak_flops):
    """Measured roofline rows: per-primitive measured seconds joined to
    the jaxpr-audit / kernel-registry FLOP+byte models. Rows are stamped
    ``source: "measured"`` — the field that keeps interpret-mode CPU
    estimates (``source: "interpret"``) and pure models (``source:
    "model"``) from ever being read as device measurements."""
    if not audit_rows:
        return []
    peak_bw = None
    rows = []
    steps = max(int(steps), 1)
    ndev = max(int(num_devices), 1)
    for arow in audit_rows:
        prim = arow.get("primitive")
        flops = float(arow.get("flops", 0.0))
        nbytes = float(arow.get("bytes", 0.0))
        us = prim_us.get(prim)
        if us is None and prim and prim.startswith("pallas::"):
            us = prim_us.get(prim[len("pallas::"):])
        if not us or flops <= 0:
            continue
        # the trace sums each device's wall time; the program's audit
        # FLOPs are global — per-device wall is the roofline clock
        sec_per_step = us / 1e6 / steps / ndev
        achieved = flops / sec_per_step
        row = {"op": prim, "source": "measured",
               "model_flops": flops, "model_bytes": nbytes,
               "measured_ms_per_step": round(us / 1e3 / steps, 4),
               "achieved_gflops_s": round(achieved / 1e9, 3),
               "intensity_flops_per_byte":
                   round(flops / nbytes, 3) if nbytes else None}
        if peak_flops:
            row["pct_of_peak"] = round(100.0 * achieved / peak_flops, 3)
            if peak_bw is None:
                peak_bw = measured_peak_bandwidth() * ndev
            ridge = peak_flops / peak_bw if peak_bw else None
            if ridge is not None and nbytes:
                row["bound"] = ("compute" if flops / nbytes >= ridge
                                else "bandwidth")
        rows.append(row)
    rows.sort(key=lambda r: -r["measured_ms_per_step"])
    return rows


def _reconcile_mfu(total_us, steps, num_devices, flops_per_step, peak_flops,
                   window_seconds):
    """Measured-vs-modeled MFU: the modeled number divides model FLOPs by
    *wall* time; the measured one divides the same FLOPs by measured
    per-device *device* time — the gap is everything the wall clock hides
    (host work, dispatch, data waits, unattributed device time)."""
    out = {"measured_device_ms_per_step": None, "measured_mfu_pct": None,
           "modeled_mfu_pct": None, "delta_pct": None}
    steps = max(int(steps), 1)
    ndev = max(int(num_devices), 1)
    if total_us:
        out["measured_device_ms_per_step"] = total_us / 1e3 / steps / ndev
    if not (flops_per_step and peak_flops):
        return out
    if total_us:
        dev_s = total_us / 1e6 / steps / ndev
        out["measured_mfu_pct"] = \
            100.0 * flops_per_step / dev_s / peak_flops
    if window_seconds:
        wall_s = window_seconds / steps
        out["modeled_mfu_pct"] = \
            100.0 * flops_per_step / wall_s / peak_flops
    if out["measured_mfu_pct"] is not None and \
            out["modeled_mfu_pct"] is not None:
        out["delta_pct"] = out["measured_mfu_pct"] - out["modeled_mfu_pct"]
    return out


_MEASURED_BW = {}


def measured_peak_bandwidth(n_mb=32, iters=4):
    """Memory bandwidth (bytes/s per device) of the default backend — the
    roofline ridge's denominator: the published figure for a device kind
    in ``mfu.DEVICE_PEAKS``, else a one-time measurement (cached per
    platform; the CPU-rig counterpart of mfu.measured_peak_flops)."""
    import jax
    import jax.numpy as jnp

    from .mfu import DEVICE_PEAKS

    published = DEVICE_PEAKS.get(jax.devices()[0].device_kind)
    if published is not None:
        return published["hbm_bytes_per_s"]
    platform = jax.default_backend()
    if platform in _MEASURED_BW:
        return _MEASURED_BW[platform]
    n = int(n_mb) * (1 << 20) // 4

    @jax.jit
    def run(x):
        def body(_, y):
            return y + jnp.float32(1.0)

        return jax.lax.fori_loop(0, iters, body, x)

    x = jnp.zeros((n,), jnp.float32)
    from ..utils.profiler import Timer

    run(x)  # compile outside the timed window
    with Timer() as t:
        t.block(run(x))
    # each iteration streams the buffer in and out once
    bw = 2.0 * n * 4 * iters / max(t.elapsed, 1e-9)
    _MEASURED_BW[platform] = bw
    return bw


def last_capture_summary():
    """The most recent capture's summary dict (flight-recorder page), or
    None when no attributed capture has completed in this process."""
    return _LAST_SUMMARY[0]


def _set_last_summary(summary):
    _LAST_SUMMARY[0] = summary


# -- the fit/predict driver ----------------------------------------------------

class ProfileSession:
    """Drives one bounded capture window inside a train/predict loop.

    The loop calls :meth:`before_step` right before each dispatch and
    :meth:`after_step` with the step's output pytree right after. The
    session waits out ``cfg.warmup`` observed steps AND a compile-quiet
    step (the window must never price XLA compiles as device time), then
    opens the window: harvests the program's compiled-HLO metadata map,
    starts the capture, counts ``cfg.steps`` steps, blocks on the last
    step's outputs, stops, attributes, and publishes. After the window
    closes every further ``before_step`` is a single attribute check —
    the out-of-window overhead the bench prices (<0.5% of a step).

    ``after_step`` returns the window's wall seconds when it just closed
    (the loop's ``profile`` badput contribution), else 0.0.
    """

    def __init__(self, cfg, layers, num_devices=1, mfu_acct=None,
                 logger=None, owner="fit"):
        self.cfg = cfg
        self.layers = frozenset(layers)
        self.num_devices = max(int(num_devices), 1)
        self.mfu_acct = mfu_acct
        self.logger = logger or logging
        self.owner = owner
        self.report = None
        self._state = "armed"        # armed -> open -> done | disabled
        self._observed = 0
        self._window_steps = 0
        self._compiles_prev = None
        self._hlo_maps = []
        self._log_dir = None
        self._t0 = None

    @property
    def pending(self):
        """True while the window has not opened yet — the loop's cheap
        out-of-window gate (one attribute read once the window is done)."""
        return self._state == "armed"

    @property
    def open(self):
        return self._state == "open"

    # -- loop hooks -----------------------------------------------------------
    def before_step(self, tracked, args_thunk, compiles_now):
        """Maybe open the window. ``tracked``: the step's TrackedJit (for
        the HLO metadata map); ``args_thunk``: zero-arg callable building
        the step's argument tuple (only called if a precompile is needed);
        ``compiles_now``: the compile registry's cumulative compile count
        (the compile-quiet gate)."""
        if self._state != "armed":
            return
        self._observed += 1
        quiet = self._compiles_prev is not None and \
            compiles_now == self._compiles_prev
        self._compiles_prev = compiles_now
        if self._observed <= self.cfg.warmup or not quiet:
            return
        self._begin(tracked, args_thunk)

    def _begin(self, tracked, args_thunk):
        self._hlo_maps = []
        if tracked is not None:
            try:
                args = args_thunk() if args_thunk is not None else ()
                for text in hlo_texts_from_tracked(tracked, *args):
                    self._hlo_maps.append(hlo_op_metadata(text)[1])
            except Exception as e:
                self.logger.warning(
                    "profiling: HLO metadata harvest failed (%s); window "
                    "will report coverage 0", e)
        # every window gets its OWN directory: jax writes each capture
        # into a timestamped subdir of the log dir, and parse_trace_dir
        # globs recursively — a reused cfg.log_dir would fold the
        # previous window's events into this one's report
        log_dir = self.cfg.log_dir
        if log_dir is not None:
            log_dir = os.path.join(
                log_dir, f"window-{os.getpid()}-{next(_WINDOW_SEQ)}")
        try:
            self._log_dir = start_capture(log_dir, owner=self.owner)
        except RuntimeError as e:
            # someone else (profile_step, a user capture) owns the
            # profiler: skip this window rather than fight over it
            self.logger.warning("profiling: window skipped: %s", e)
            self._state = "disabled"
            return
        self._t0 = time.perf_counter()
        self._state = "open"
        self._window_steps = 0

    def after_step(self, outputs, epoch=None):
        if self._state != "open":
            return 0.0
        self._window_steps += 1
        if self._window_steps < self.cfg.steps:
            return 0.0
        return self._finish(outputs, epoch=epoch)

    def close(self, outputs=None, epoch=None):
        """Force-close an open window (epoch boundary / loop exit). Safe
        to call in any state; returns the window seconds if one closed."""
        if self._state != "open":
            return 0.0
        if self._window_steps == 0:
            # nothing captured: drop the trace, don't publish a 0-step row
            stop_capture()
            self._state = "done"
            return time.perf_counter() - self._t0
        return self._finish(outputs, epoch=epoch)

    # -- window close + publish -----------------------------------------------
    def _finish(self, outputs, epoch=None):
        """Close the window. Returns the FULL observation cost — capture
        wall plus the inline post-processing (gzip trace parse, report
        build, first-time peak/bandwidth probes) — so the `profile`
        badput bucket prices everything the profiler took from the step
        loop, not just the traced span ("observation is not
        throughput")."""
        import jax

        if outputs is not None:
            # the trace must hold the window's full device time, not its
            # dispatch prefix
            jax.block_until_ready(outputs)
        log_dir, seconds = stop_capture()
        self._state = "done"
        t_post = time.perf_counter()
        try:
            trace_rows = parse_trace_dir(log_dir)
        except Exception as e:
            self.logger.warning("profiling: trace parse failed: %s", e)
            return seconds + (time.perf_counter() - t_post)
        acct = self.mfu_acct
        report = build_report(
            trace_rows, self._hlo_maps, self.layers, steps=self._window_steps,
            window_seconds=seconds,
            audit_rows=getattr(acct, "audit_rows", None),
            flops_per_step=getattr(acct, "flops_per_step", None),
            num_devices=self.num_devices,
            peak_flops=acct.peak_flops if acct is not None
            and getattr(acct, "flops_per_step", None) else None,
            log_dir=log_dir, epoch=epoch)
        self.report = report
        self.publish(report)
        return seconds + (time.perf_counter() - t_post)

    def publish(self, report):
        h = _hub()
        summary = report.to_dict(top_k=self.cfg.top_k)
        h.emit("profile", phase="summary", owner=self.owner, **summary)
        _set_last_summary({"owner": self.owner, **summary})
        if self.cfg.gauges:
            h.gauge("profile_coverage_pct", report.coverage_pct)
            h.gauge("profile_device_ms", report.total_us / 1e3)
            h.gauge("profile_unattributed_ms", report.unattributed_us / 1e3)
            h.gauge("profile_window_seconds", report.window_seconds)
            for layer, us in report.layers.items():
                h.gauge("profile_layer_device_ms", us / 1e3, layer=layer)
            if report.mfu.get("measured_mfu_pct") is not None:
                h.gauge("profile_measured_mfu_pct",
                        report.mfu["measured_mfu_pct"])
        self.logger.info("%s", report.table(top_k=self.cfg.top_k))
        mfu = report.mfu
        if mfu.get("measured_mfu_pct") is not None and \
                mfu.get("modeled_mfu_pct") is not None:
            self.logger.info(
                "profile MFU: measured %.2f%% (device clock) vs modeled "
                "%.2f%% (wall clock), delta %+.2f%%",
                mfu["measured_mfu_pct"], mfu["modeled_mfu_pct"],
                mfu["delta_pct"])
