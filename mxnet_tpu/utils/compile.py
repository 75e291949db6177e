"""Compile-management subsystem: persistent cache, program registry, AOT
warmup, shape-padding policy, and a recompile guard.

The runtime hot path (fusion, donation, feed/compute overlap) is tuned
elsewhere; this module attacks the OTHER cost axis — XLA compile time. On a
real TPU pod a ResNet-class program compiles in minutes, every process
restart pays it again (and the resilience layer made restarts routine), and
any shape drift — tail batches, a new bucket key, eval shapes — silently
triggers a fresh compile mid-epoch. The reference design's answer was the
per-shape cached-executor model (SURVEY §1: GraphExecutor "cached engine
ops"); the TPU-native answer is four cooperating pieces:

  1. **Persistent compilation cache** — ``configure_persistent_cache``,
     called once at package import, so warm process starts deserialize
     executables from disk instead of re-running XLA. The directory is
     placed from outside: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
     itself uses it and this code sets nothing; otherwise the cache lives
     at a fixed path inside the checkout, ``.jax_cache`` (the path is part
     of JAX's cache key, so it never moves). ``MXNET_TPU_COMPILE_CACHE=0``
     turns the in-checkout cache off.

  2. **Program registry** — every jit program the framework dispatches goes
     through :func:`tracked_jit`, which attributes cache hits/misses,
     compile counts, and compile-seconds (via ``jax.monitoring``) to a
     stable program label: ``(graph fingerprint, shapes/dtypes signature,
     fusion flags)``. ``Executor``, ``FeedForward`` train/pred/eval steps,
     and ``BucketingFeedForward`` all share the one registry, so
     ``compile_stats()`` answers "what compiled, when, for how long" for
     the whole process.

  3. **AOT warmup** — :meth:`TrackedJit.precompile` traces, lowers and
     compiles a program ahead of time (``.trace().lower().compile()``,
     each stage a ``telemetry.phase()``) and keeps the executable for
     signature-matched dispatch, so ``FeedForward.precompile()`` /
     ``Executor.precompile()`` can compile every bucket/eval program up
     front (and in parallel threads) instead of stalling step 1 of each
     shape. Every other XLA compile leaves one ``compile.backend`` record
     in the same span ring (the registry's ``jax.monitoring`` listener).

  4. **PadPolicy + RecompileTracker** — the policy folds odd shapes into
     known ones (pad-to-bucket, or next-pow2 to bound the program count
     under arbitrary drift); the tracker observes every tracked jit cache
     miss, logs it, and — armed in tests — turns "zero recompiles in steady
     state" from a hope into an enforced invariant.

This module deliberately imports only jax + stdlib at import time so every
layer (executor, model, bucketing, io, monitor, bench) can use it without
import cycles; the span primitive (``telemetry.timeline``) is imported
where a compile uses it.
"""

from __future__ import annotations

import contextlib
import hashlib
import logging
import os
import threading

import jax
import numpy as np

from ..analysis.lockwatch import named_lock
from ..base import MXNetError

__all__ = [
    "configure_persistent_cache", "persistent_cache_dir",
    "CHECKOUT_CACHE_DIR",
    "ProgramRegistry", "registry", "compile_stats", "reset_compile_stats",
    "tracked_jit", "TrackedJit", "graph_fingerprint",
    "RecompileTracker", "RecompileError",
    "PadPolicy",
    "MEMORY_PLAN_FIELDS", "memory_plan_from_compiled",
    "add_memory_plan_listener",
]


# -- 1. persistent on-disk XLA compilation cache -------------------------------

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_OFF_VALUES = ("", "0", "off", "false", "no")
_ON_VALUES = ("1", "on", "true", "yes")


def configure_persistent_cache():
    """Resolve where JAX's persistent compilation cache lives; the package
    calls this once at import. Returns the active directory or None.

    ``JAX_COMPILATION_CACHE_DIR`` in the environment wins outright: JAX
    read it on its own at import, so nothing is set in code (nor its
    thresholds — the ``JAX_PERSISTENT_CACHE_*`` variables stay in charge).
    Otherwise the cache is :data:`CHECKOUT_CACHE_DIR` unless
    ``MXNET_TPU_COMPILE_CACHE`` is falsy; ``MXNET_TPU_COMPILE_CACHE_MIN_SEC``
    (default 0.5) keeps programs cheaper to compile than to read back out
    of it.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and os.environ.get(
            "MXNET_TPU_COMPILE_CACHE", "1").strip().lower() not in _OFF_VALUES:
        # JAX creates the directory on its first write, and only warns
        # when it cannot (a read-only install runs without a cache)
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(os.environ.get("MXNET_TPU_COMPILE_CACHE_MIN_SEC", "0.5")))
    return persistent_cache_dir()


def persistent_cache_dir():
    """The directory JAX's persistent cache uses, or None when it is off:
    JAX's own setting, whoever set it."""
    return jax.config.jax_compilation_cache_dir


# -- 2. program registry -------------------------------------------------------

_UNTRACKED = "<untracked>"

# the jax.monitoring events the registry reads, by a part of their names
# (every other name, among them the per-jaxpr trace and lowering
# durations, returns at once)
_BACKEND_COMPILE = "backend_compile_duration"
_TIME_SAVED = "compile_time_saved"
_CACHE_ASKED = "/compile_requests_use_cache"
_CACHE_HIT = "/cache_hits"


def _cache_outcome(before, after):
    """``"off"`` when no compile between the two marks asked the
    persistent cache or it has no directory (JAX asks it all the same),
    ``"hit"`` when every one that asked was served from it, else
    ``"miss"``."""
    asked, hits = after[0] - before[0], after[1] - before[1]
    if not asked or not persistent_cache_dir():
        return "off"
    return "hit" if hits == asked else "miss"


# Static memory plans (ISSUE 9): every AOT-compiled program registers its
# XLA memory_analysis() breakdown here, keyed by the same program label as
# the compile stats — the framework's answer to the reference's
# GraphExecutor::Print "Total N MB allocated" line, but queryable without
# re-lowering anything. The telemetry layer subscribes via
# add_memory_plan_listener to export plans as hub gauges/events.
MEMORY_PLAN_FIELDS = ("argument_bytes", "output_bytes", "temp_bytes",
                      "generated_code_bytes", "alias_bytes", "total_bytes")

_PLAN_ATTRS = (("argument_bytes", "argument_size_in_bytes"),
               ("output_bytes", "output_size_in_bytes"),
               ("temp_bytes", "temp_size_in_bytes"),
               ("generated_code_bytes", "generated_code_size_in_bytes"),
               ("alias_bytes", "alias_size_in_bytes"))

_MEMORY_PLAN_LISTENERS: list = []


def add_memory_plan_listener(fn):
    """Register ``fn(label, plan_dict)`` to run whenever a program's memory
    plan is (re)recorded — the telemetry layer's hook; utils/compile itself
    stays jax+stdlib only."""
    _MEMORY_PLAN_LISTENERS.append(fn)
    return fn


def memory_plan_from_compiled(compiled):
    """Extract a memory plan dict from a compiled executable's
    ``memory_analysis()``. Returns None when the backend doesn't expose it
    (the caller degrades to "unavailable", never fails). ``total_bytes``
    matches Executor.debug_str's historical "Total" line: temp + output —
    what the program itself allocates beyond its arguments."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return None
    if mem is None:
        return None
    plan = {field: int(getattr(mem, attr, 0) or 0)
            for field, attr in _PLAN_ATTRS}
    plan["total_bytes"] = plan["temp_bytes"] + plan["output_bytes"]
    return plan


def _label_counters():
    return {"hits": 0, "misses": 0, "aot_hits": 0, "precompiles": 0,
            "compiles": 0, "compile_seconds": 0.0, "signatures": set()}


class ProgramRegistry:
    """Process-wide compile accounting shared by every tracked program.

    Counters per program label (hit = dispatch served from the jit cache
    or an AOT executable; miss = the call compiled) plus compile-seconds
    attribution: ``jax.monitoring``'s ``backend_compile`` duration events
    are credited to whichever tracked program is currently dispatching on
    this thread (``<untracked>`` otherwise — e.g. op-by-op jnp dispatch).
    Persistent-cache hits and saved seconds are folded in from the same
    event stream. Each such compile outside ``TrackedJit.precompile``'s
    own ``setup.compile.backend`` span also leaves one ``compile.backend``
    record in the telemetry span ring (``label``, ``fun``: jax's name of
    the function, ``cache``: ``hit`` / ``miss`` / ``off``).
    """

    def __init__(self):
        self._lock = named_lock("compile.ProgramRegistry")
        self._tls = threading.local()
        self.reset()

    # -- label attribution (thread-local: parallel precompile threads each
    # credit their own program) ----------------------------------------------
    def _stack(self):
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def current_label(self):
        stack = self._stack()
        return stack[-1] if stack else None

    @contextlib.contextmanager
    def attribute(self, label):
        stack = self._stack()
        stack.append(label)
        try:
            yield
        finally:
            stack.pop()

    def cache_marks(self):
        """``(requests, hits)`` of the persistent cache so far on this
        thread: its events fire on the compiling thread, before the
        compile's ``backend_compile_duration``."""
        tls = self._tls
        return getattr(tls, "cache_asked", 0), getattr(tls, "cache_hits", 0)

    # -- event sinks (wired to jax.monitoring once, below) --------------------
    def _on_duration(self, name, seconds, fun_name=None, **_):
        if _BACKEND_COMPILE in name:
            # telemetry imports this module while it loads
            from ..telemetry import timeline

            label = self.current_label() or _UNTRACKED
            marks = self.cache_marks()
            cache = _cache_outcome(
                getattr(self._tls, "cache_at_compile", (0, 0)), marks)
            self._tls.cache_at_compile = marks
            with self._lock:
                c = self._labels.setdefault(label, _label_counters())
                c["compiles"] += 1
                c["compile_seconds"] += seconds
                self._totals["compiles"] += 1
                self._totals["compile_seconds"] += seconds
            timeline.record_compile(seconds, label=label, fun=fun_name,
                                    cache=cache)
        elif _TIME_SAVED in name:
            with self._lock:
                self._totals["persistent_cache_saved_seconds"] += seconds

    def _on_event(self, name, **_):
        tls = self._tls
        if name.endswith(_CACHE_ASKED):
            tls.cache_asked = getattr(tls, "cache_asked", 0) + 1
        elif name.endswith(_CACHE_HIT):
            tls.cache_hits = getattr(tls, "cache_hits", 0) + 1
            with self._lock:
                self._totals["persistent_cache_hits"] += 1

    # -- dispatch accounting --------------------------------------------------
    def record_call(self, label, kind, signature=None):
        """kind: 'hit' | 'miss' | 'aot_hit' | 'precompile'."""
        with self._lock:
            c = self._labels.setdefault(label, _label_counters())
            if kind == "hit":
                c["hits"] += 1
                self._totals["hits"] += 1
            elif kind == "aot_hit":
                c["aot_hits"] += 1
                c["hits"] += 1
                self._totals["hits"] += 1
            elif kind == "miss":
                c["misses"] += 1
                self._totals["misses"] += 1
                if signature is not None:
                    c["signatures"].add(signature)
            elif kind == "precompile":
                c["precompiles"] += 1
                if signature is not None:
                    c["signatures"].add(signature)
        if kind == "miss":
            _notify_trackers(label, signature)

    # -- memory plans (ISSUE 9) -----------------------------------------------
    def record_memory_plan(self, label, plan):
        """Store a program's static memory plan under its compile label
        (idempotent re-record wins) and notify plan listeners."""
        plan = dict(plan)
        with self._lock:
            self._memory_plans[label] = plan
        for fn in list(_MEMORY_PLAN_LISTENERS):
            try:
                fn(label, dict(plan))
            except Exception:  # a telemetry sink must not fail a compile
                logging.debug("memory-plan listener failed for %r", label,
                              exc_info=True)

    def memory_plan_for(self, label):
        with self._lock:
            plan = self._memory_plans.get(label)
            return None if plan is None else dict(plan)

    def memory_plans(self):
        with self._lock:
            return {k: dict(v) for k, v in self._memory_plans.items()}

    # -- reporting ------------------------------------------------------------
    def reset(self):
        with getattr(self, "_lock", contextlib.nullcontext()):
            self._labels = {}
            self._memory_plans = {}
            self._totals = {"hits": 0, "misses": 0, "compiles": 0,
                            "compile_seconds": 0.0,
                            "persistent_cache_hits": 0,
                            "persistent_cache_saved_seconds": 0.0}

    def snapshot(self):
        """Cheap totals copy, for before/after diffing (epoch logs)."""
        with self._lock:
            return dict(self._totals)

    def stats(self):
        """Full per-program report: counters + distinct compiled signatures."""
        with self._lock:
            labels = {
                k: {**{f: v for f, v in c.items() if f != "signatures"},
                    "programs": len(c["signatures"])}
                for k, c in self._labels.items()
            }
            return {**self._totals, "per_function": labels}

    def compiles_for(self, label):
        with self._lock:
            c = self._labels.get(label)
            return 0 if c is None else c["compiles"]


_REGISTRY = None
_LISTENERS_INSTALLED = False


def _install_listeners(reg):
    global _LISTENERS_INSTALLED
    if _LISTENERS_INSTALLED:
        return
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(reg._on_duration)
    monitoring.register_event_listener(reg._on_event)
    _LISTENERS_INSTALLED = True


def registry() -> ProgramRegistry:
    """The process-wide ProgramRegistry (created on first use)."""
    global _REGISTRY
    if _REGISTRY is None:
        _REGISTRY = ProgramRegistry()
        _install_listeners(_REGISTRY)
    return _REGISTRY


def compile_stats():
    """Aggregated compile accounting for this process (see ProgramRegistry)."""
    return registry().stats()


def reset_compile_stats():
    registry().reset()


def graph_fingerprint(symbol) -> str:
    """Stable identity of a compiled graph: the serialized symbol plus the
    graph-rewrite flags that change what actually lowers (fusion, remat).
    Program labels key on this so the registry distinguishes 'same symbol,
    different fusion config' — the reference's cached-engine-op key.

    Graphs that cannot serialize (_Native ops holding live python objects)
    fall back to a structural identity (topo-ordered node names + op
    types) — they can't ride the persistent cache anyway, and the label
    only feeds accounting."""
    try:
        graph = symbol.tojson()
    except Exception:
        graph = ";".join(
            f"{n.name}:{'var' if n.is_variable else type(n.op).__name__}"
            for n in symbol._topo())
    payload = "|".join([
        graph,
        "fuse=" + os.environ.get("MXNET_TPU_FUSE", "1"),
        "remat=" + os.environ.get("MXNET_TPU_REMAT", ""),
    ])
    return hashlib.sha1(payload.encode()).hexdigest()[:12]


# -- 3. tracked jit + AOT warmup ----------------------------------------------

def _leaf_spec(leaf):
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        # python scalars/static leaves: typed but never AOT-matched
        return ("py", type(leaf).__name__)
    return (tuple(shape), str(dtype))


class TrackedJit:
    """``jax.jit`` with registry accounting and AOT warmup.

    - ``__call__`` dispatches like the jitted function, classifying each
      call as a cache hit or miss (miss = the jit trace cache grew during
      the call, i.e. a compile happened) and crediting compile-seconds to
      this program's label.
    - ``precompile(*abstract_args)`` traces, lowers and compiles ahead of
      time (``.trace().lower().compile()``) and keeps the executable;
      later calls whose argument signature matches dispatch straight to
      it — the jit cache is never consulted, so step 1 of a warmed shape
      pays zero compile.
    """

    def __init__(self, fn, label=None, registry_=None, **jit_kwargs):
        self.label = label or getattr(fn, "__name__", "jit_fn")
        self._registry = registry_ if registry_ is not None else registry()
        self._jitted = jax.jit(fn, **jit_kwargs)
        self._aot = {}

    def signature(self, args, kwargs):
        flat, treedef = jax.tree_util.tree_flatten((args, kwargs))
        return (treedef, tuple(_leaf_spec(leaf) for leaf in flat))

    def _cache_size(self):
        return self._jitted._cache_size()

    def __call__(self, *args, **kwargs):
        reg = self._registry
        if self._aot:
            if len(self._aot) == 1:
                # hot-path fast case (one warmed program per TrackedJit is
                # the norm): dispatch straight to the executable — its own
                # argument check replaces the signature lookup, so steady
                # state pays no tree_flatten over the full state pytree
                compiled = next(iter(self._aot.values()))
                try:
                    out = compiled(*args, **kwargs)
                except TypeError:
                    pass  # shape/layout drift: ordinary jit path below
                else:
                    reg.record_call(self.label, "aot_hit")
                    return out
            else:
                key = self.signature(args, kwargs)
                compiled = self._aot.get(key)
                if compiled is not None:
                    try:
                        out = compiled(*args, **kwargs)
                    except TypeError:
                        # sharding drift vs the warmed executable: drop the
                        # stale entry and take the ordinary jit path
                        self._aot.pop(key, None)
                    else:
                        reg.record_call(self.label, "aot_hit")
                        return out
        before = self._cache_size()
        with reg.attribute(self.label):
            out = self._jitted(*args, **kwargs)
        if self._cache_size() > before:
            reg.record_call(self.label, "miss",
                            signature=self.signature(args, kwargs))
        else:
            reg.record_call(self.label, "hit")
        return out

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def precompile(self, *args, **kwargs):
        """AOT-compile for the given (abstract or concrete) arguments and
        register the executable for signature-matched dispatch. Idempotent
        per signature; returns the compiled executable.

        The three stages are spans on the calling thread, each with this
        program's ``label``: ``setup.compile.trace`` (the jaxpr: every
        operator's Python and every Pallas kernel body runs here),
        ``setup.compile.lower`` (StableHLO with the Mosaic payloads) and
        ``setup.compile.backend`` (XLA's compile or the persistent cache's
        read; ``cache`` = ``hit`` / ``miss`` / ``off``). ``trace().lower()``
        is the module, and so the cache key, that ``lower()`` gives."""
        key = self.signature(args, kwargs)
        if key in self._aot:
            return self._aot[key]
        # telemetry imports this module while it loads
        from ..telemetry.timeline import phase

        reg, label = self._registry, self.label
        with reg.attribute(label):
            with phase("setup.compile.trace", label=label):
                traced = self._jitted.trace(*args, **kwargs)
            with phase("setup.compile.lower", label=label):
                lowered = traced.lower()
            with phase("setup.compile.backend", label=label) as span:
                marks = reg.cache_marks()
                compiled = lowered.compile()
                span.attrs["cache"] = _cache_outcome(marks,
                                                     reg.cache_marks())
        self._aot[key] = compiled
        reg.record_call(label, "precompile", signature=key)
        plan = memory_plan_from_compiled(compiled)
        if plan is not None:
            # every AOT program ships its HBM plan (per-pad-bucket programs
            # included): argument/output/temp/code bytes, queryable via the
            # registry + telemetry without re-lowering (ISSUE 9)
            reg.record_memory_plan(label, plan)
        return compiled

    @property
    def aot_programs(self):
        return len(self._aot)

    @property
    def jitted(self):
        """The underlying ``jax.jit`` object — the traceable surface for
        read-only consumers (``jax.make_jaxpr`` in the shard audit); call
        through the TrackedJit itself to keep registry accounting."""
        return self._jitted

    def optimized_hlo(self, *args, **kwargs) -> str:
        """Optimized-HLO text of the warmed program for this signature —
        AOT-compiling it first if needed (idempotent, registry-priced).
        This is what the mxlint Pass 5 collective reconciliation audits:
        the text of the EXACT executable signature-matched dispatch will
        run, not a fresh re-lowering."""
        return self.precompile(*args, **kwargs).as_text()

    def is_warm(self, *args, **kwargs) -> bool:
        """Is an AOT executable already registered for this argument
        signature? The elastic resize path asks this before re-warming:
        growing back to a previously-seen axis size finds the old world's
        programs still warm and skips the lower+compile entirely."""
        return self.signature(args, kwargs) in self._aot


def tracked_jit(fn, label=None, **jit_kwargs) -> TrackedJit:
    """Drop-in ``jax.jit`` replacement that reports to the program registry
    (and to any armed RecompileTracker)."""
    return TrackedJit(fn, label=label, **jit_kwargs)


# -- 4a. recompile guard -------------------------------------------------------

class RecompileError(MXNetError):
    """An armed RecompileTracker observed a jit compile (steady-state
    invariant violated)."""


_ACTIVE_TRACKERS: list["RecompileTracker"] = []


def _notify_trackers(label, signature):
    for tracker in list(_ACTIVE_TRACKERS):
        tracker._observe(label, signature)


class RecompileTracker:
    """Observes jit cache misses on every tracked program.

    Usage: warm the programs up (first epoch / ``precompile``), then
    ``arm()`` — or use as a context manager. Every subsequent tracked miss
    is recorded in ``recompiles``, logged (and mirrored into an installed
    ``Monitor``'s stat queue), and — with ``raise_on_recompile=True``, the
    test configuration — raised as :class:`RecompileError`, making "zero
    recompiles in steady state" an enforced invariant.
    """

    def __init__(self, raise_on_recompile=False, logger=None, monitor=None):
        self.raise_on_recompile = raise_on_recompile
        self.logger = logger or logging.getLogger(__name__)
        self.monitor = monitor
        self.recompiles: list[tuple] = []
        self.armed = False

    def arm(self):
        self.armed = True
        if self not in _ACTIVE_TRACKERS:
            _ACTIVE_TRACKERS.append(self)
        return self

    def disarm(self):
        self.armed = False
        if self in _ACTIVE_TRACKERS:
            _ACTIVE_TRACKERS.remove(self)
        return self

    def __enter__(self):
        return self.arm()

    def __exit__(self, *exc):
        self.disarm()
        return False

    def _observe(self, label, signature):
        if not self.armed:
            return
        self.recompiles.append((label, signature))
        self.logger.warning(
            "RecompileTracker: %r compiled while armed (new signature: %s) "
            "— steady-state shape drift", label,
            signature[1] if signature else "?")
        if self.monitor is not None:
            # surface through the Monitor's stat rows at its next
            # toc()/collect_compiles() — NOT .queue directly, which toc()
            # rebinds (events appended there would be silently lost)
            sink = getattr(self.monitor, "_recompile_events", None)
            if sink is None:
                sink = self.monitor.queue  # duck-typed monitors
            sink.append((getattr(self.monitor, "step", 0),
                         f"recompile/{label}", 1))
        if self.raise_on_recompile:
            raise RecompileError(
                f"recompile of {label!r} while RecompileTracker armed "
                f"(signature {signature[1] if signature else '?'}); pad "
                "tail batches (PadPolicy) or precompile all shapes up front")

    def assert_no_recompiles(self):
        if self.recompiles:
            raise RecompileError(
                f"{len(self.recompiles)} recompile(s) while armed: "
                + ", ".join(label for label, _ in self.recompiles))


# -- 4b. shape-padding policy --------------------------------------------------

def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n - 1).bit_length())


class PadPolicy:
    """Fold odd shapes into known ones instead of compiling fresh programs.

    Modes:
      - ``"bucket"``: pad to the configured bucket/batch size — ONE program
        per bucket, period (tail batches pad up to the full batch).
      - ``"pow2"``: pad to the next power of two — bounds the program count
        at log2(max) under arbitrary drift (the classic serving-side
        compromise when a single bucket size would over-pad).

    Used two ways: ``fit`` pads tail batches (rows) and masks the padded
    rows out of the loss and metric (see ops/loss.py ``fwd_masked`` — the
    loss heads zero padded rows' injected gradients, so the update equals
    the unpadded batch exactly; BatchNorm batch statistics are the one
    approximation, and pad rows repeat real rows to stay in-distribution);
    ``BucketSentenceIter`` uses :meth:`round_length` for bucket assignment.
    """

    MODES = ("bucket", "pow2")

    def __init__(self, mode="bucket"):
        if mode not in self.MODES:
            raise MXNetError(
                f"PadPolicy mode must be one of {self.MODES}, got {mode!r}")
        self.mode = mode

    def __repr__(self):
        return f"PadPolicy(mode={self.mode!r})"

    def key(self):
        """Hashable identity (program-cache key component)."""
        return ("pad_policy", self.mode)

    @classmethod
    def resolve(cls, value):
        """Normalize fit()'s ``pad_policy`` argument: None -> env gate
        ``MXNET_TPU_PAD_POLICY`` (unset/falsy = off, else the mode name);
        True -> bucket mode; str -> that mode; PadPolicy -> itself."""
        if value is None:
            raw = os.environ.get("MXNET_TPU_PAD_POLICY", "").strip().lower()
            if raw in _OFF_VALUES:
                return None
            value = "bucket" if raw in _ON_VALUES else raw
        if value is False:
            return None
        if value is True:
            return cls()
        if isinstance(value, cls):
            return value
        return cls(str(value))

    # -- rounding -------------------------------------------------------------
    def round_rows(self, rows: int, target: int) -> int:
        """Padded row count for a batch of ``rows`` given the configured
        batch size ``target``."""
        if rows >= target:
            return rows
        if self.mode == "pow2":
            return min(target, _next_pow2(rows))
        return target

    def round_length(self, length: int, buckets=None):
        """Bucket assignment for a sequence of ``length``: the smallest
        configured bucket that fits (bucket mode), or the next power of two
        (pow2 mode; clamped into ``buckets`` when given). Returns None when
        no bucket fits (caller drops the sequence)."""
        if self.mode == "pow2":
            target = _next_pow2(length)
            if not buckets:
                return target
            for b in buckets:
                if target <= b:
                    return b
            return None
        if not buckets:
            raise MXNetError("PadPolicy('bucket').round_length needs buckets")
        for b in buckets:
            if length <= b:
                return b
        return None

    # -- batch padding --------------------------------------------------------
    def pad_arrays(self, arrays: dict, target_rows: int, pad: int = 0):
        """Pad every array in ``arrays`` along axis 0 up to ``target_rows``
        by repeating the last row (keeps e.g. BatchNorm statistics
        in-distribution — the rows are masked out of loss/metric anyway).

        ``pad`` is the iterator-reported pad already PRESENT in the arrays
        (wrap-around rows). Returns ``(padded_arrays, num_valid)`` where
        ``num_valid`` counts the leading genuinely-valid rows.
        """
        rows = None
        for v in arrays.values():
            shape = getattr(v, "shape", None)
            if shape:
                rows = int(shape[0])
                break
        if rows is None:
            raise MXNetError("pad_arrays: no array inputs to pad")
        num_valid = rows - int(pad)
        extra = int(target_rows) - rows
        if extra <= 0:
            return arrays, num_valid
        out = {}
        for k, v in arrays.items():
            a = np.asarray(v)
            if a.ndim == 0 or a.shape[0] != rows:
                out[k] = v
                continue
            out[k] = np.concatenate(
                [a, np.repeat(a[-1:], extra, axis=0)], axis=0)
        return out, num_valid
