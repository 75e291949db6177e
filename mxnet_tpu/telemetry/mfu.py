"""MFU / goodput accounting: the two numbers a TPU user asks for first.

**MFU** (model FLOPs utilization) = model FLOPs actually computed per
second / hardware peak FLOPs. The numerator comes from the jaxpr FLOP
table the static-analysis layer already produces
(``analysis.jaxpr_audit``): the fused train step is traced ONCE (shapes
only, no execution) and its per-primitive FLOP rows summed — forward,
backward, and the fused optimizer update all included, because they are
all in the one program. The denominator resolves, in order:

  1. ``MXNET_TPU_PEAK_FLOPS`` — peak FLOP/s **per device**, for a chip
     the table does not know;
  2. :data:`DEVICE_PEAKS`, the published peak keyed by the device's
     ``device_kind``;
  3. on the CPU backend only, a one-time measured matmul rate (a
     datasheet number would be fiction there; the ratio is rig-relative).

An accelerator whose kind is not in the table has no peak: MFU is
reported as unavailable, never computed against a guess.

Caveat that ships with the number (see doc/developer-guide/telemetry.md):
the jaxpr table counts *pre-fusion* model FLOPs — what the model
mathematically needs — so MFU stays comparable across runs; XLA may
compute slightly more (recomputed remat blocks) or fewer (algebraic
simplification). On CPU rigs the measured peak makes MFU a rig-relative
ratio, not a datasheet fraction.

Custom Pallas kernels (flash attention, the fused comm/optimizer
kernels) are priced through the kernel registry
(ops/pallas/registry.py): the audit attributes each registered
``pallas_call`` from its FLOP model instead of recursing into one grid
cell — before the registry, a flash-attention transformer's MFU
under-reported by the whole attention FLOP count
(doc/developer-guide/kernels.md).

**Goodput** = fraction of wall time spent on steps that advanced
training. The badput side is attributed from the registries that already
know: XLA compile seconds (compile registry delta), non-finite skipped
steps and step retries (resilience guard stats), and data stalls (the
timeline's data-wait phase).
"""

from __future__ import annotations

import logging
import os

from .hub import hub as _hub

__all__ = ["MFUAccountant", "DEVICE_PEAKS", "device_peak_flops",
           "resolve_peak_flops", "measured_peak_flops",
           "record_compile_badput"]

# Published per-chip peaks keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_peak_flops(device=None):
    """Published bf16 peak FLOP/s of ``device`` (default: the first
    device), or None when its kind is not in :data:`DEVICE_PEAKS`."""
    import jax

    if device is None:
        device = jax.devices()[0]
    row = DEVICE_PEAKS.get(device.device_kind)
    return None if row is None else row["bf16_flops"]

_MEASURED_PEAK = {}  # backend platform -> measured FLOP/s per device

# Watermark on the compile registry's CUMULATIVE compile-seconds: both the
# Speedometer (per reporting window) and epoch_report (per epoch) observe
# the same registry deltas, so counting each observation would double-book
# a compile into badput_compile_seconds_total. Every counter increment
# goes through record_compile_badput, which only counts seconds above the
# high-water mark.
import threading as _threading
from ..analysis.lockwatch import named_lock as _named_lock

_COMPILE_WM_LOCK = _named_lock("telemetry.mfu.compile_wm")
_COMPILE_WM = [None]  # None until the first observation window


def record_compile_badput(total_seconds, window_seconds, epoch=None):
    """Fold the compile seconds in ``(total - window, total]`` that have
    not been counted yet into ``badput_compile_seconds_total`` (+ a
    ``badput`` event). ``total_seconds`` is the compile registry's
    cumulative counter; idempotent across overlapping observers. Returns
    the newly-counted seconds."""
    with _COMPILE_WM_LOCK:
        if _COMPILE_WM[0] is None or total_seconds < _COMPILE_WM[0]:
            # first observation — or the cumulative counter went BACKWARD,
            # which means the compile registry was reset
            # (utils.compile.reset_compile_stats): re-baseline instead of
            # letting the stale high-water mark eat every future window
            _COMPILE_WM[0] = total_seconds - window_seconds
        start = max(_COMPILE_WM[0], total_seconds - window_seconds)
        delta = total_seconds - start
        if delta <= 0:
            return 0.0
        _COMPILE_WM[0] = total_seconds
    h = _hub()
    h.counter("badput_compile_seconds_total", delta)
    h.emit("badput", reason="compile", seconds=delta, epoch=epoch)
    return delta


def measured_peak_flops(n=384, iters=8):
    """One-time matmul-derived FLOP/s estimate for one device of the
    default backend (cached per platform) — the CPU rigs' denominator;
    at this size an accelerator is dispatch-bound, so
    :func:`resolve_peak_flops` never consults it there. Small n keeps it
    under ~0.2s on CPU while saturating the unit enough for a usable
    ceiling."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    platform = jax.default_backend()
    if platform in _MEASURED_PEAK:
        return _MEASURED_PEAK[platform]

    @jax.jit
    def run(a):
        def body(_, x):
            return jnp.tanh(x @ a)

        return jax.lax.fori_loop(0, iters, body, a)

    a = jnp.asarray(np.random.RandomState(0)
                    .randn(n, n).astype(np.float32))
    from ..utils.profiler import Timer

    run(a)  # compile outside the timed window
    with Timer() as t:
        t.block(run(a))
    flops = 2.0 * n * n * n * iters
    peak = flops / max(t.elapsed, 1e-9)
    _MEASURED_PEAK[platform] = peak
    logging.info("telemetry: measured matmul peak %.2f GFLOP/s on %s",
                 peak / 1e9, platform)
    return peak


def resolve_peak_flops(num_devices=1):
    """Aggregate peak FLOP/s for ``num_devices`` devices (module
    docstring: env override, published table, measured on CPU), or None
    for an accelerator the table does not list."""
    import jax

    raw = os.environ.get("MXNET_TPU_PEAK_FLOPS", "").strip()
    if raw:
        per_device = float(raw)
    else:
        dev = jax.devices()[0]
        per_device = device_peak_flops(dev)
        if per_device is None and dev.platform == "cpu":
            per_device = measured_peak_flops()
        if per_device is None:
            logging.warning(
                "telemetry: no published peak for device kind %r; MFU is "
                "unavailable (add it to telemetry.mfu.DEVICE_PEAKS or set "
                "MXNET_TPU_PEAK_FLOPS)", dev.device_kind)
            return None
    return per_device * max(int(num_devices), 1)


class MFUAccountant:
    """Per-run FLOP/step resolution + per-epoch MFU/goodput reporting.

    ``maybe_trace(jitted, args)`` is called by the train loop right before
    the FIRST dispatch of each program configuration: ``jax.make_jaxpr``
    traces the exact step about to run (abstract — no compute, no
    donation) and the jaxpr audit's cost table gives its FLOPs. Traced
    once per program; failures degrade to the compiled executable's own
    ``cost_analysis`` and then to None (MFU reported as n/a) rather than
    ever failing the step."""

    def __init__(self, num_devices=1, peak_flops=None):
        self.num_devices = max(int(num_devices), 1)
        self._peak = peak_flops
        self.flops_per_step = None
        self.bytes_per_step = None
        # per-primitive FLOP/byte rows of the traced program — the
        # measured-roofline join key for the device-time profiler
        # (telemetry/profiling.py): measured per-op seconds against these
        # modeled costs give achieved-FLOP/s and %-of-peak per op
        self.audit_rows = None

    @property
    def peak_flops(self):
        if self._peak is None:
            self._peak = resolve_peak_flops(self.num_devices)
        return self._peak

    def set_num_devices(self, num_devices):
        """Elastic resize: the world changed size mid-run. The aggregate
        peak re-resolves for the new device count; FLOPs/step stay — the
        fused step computes the same GLOBAL batch regardless of how many
        devices the dp axis splits it over, so the model-FLOPs numerator
        is resize-invariant."""
        num_devices = max(int(num_devices), 1)
        if num_devices != self.num_devices:
            self.num_devices = num_devices
            self._peak = None
        return self.num_devices

    # -- FLOP resolution ------------------------------------------------------
    def maybe_trace(self, jitted, args):
        """Resolve FLOPs/step from the program about to dispatch (no-op
        once resolved)."""
        if self.flops_per_step is not None:
            return self.flops_per_step
        try:
            import jax

            from ..analysis import jaxpr_audit

            closed = jax.make_jaxpr(lambda *a: jitted(*a))(*args)
            report = jaxpr_audit.audit_jaxpr(closed)
            self.flops_per_step = float(report.totals["flops"])
            self.bytes_per_step = float(report.totals["bytes"])
            self.audit_rows = list(report.rows)
        except Exception as e:  # audit drift must never fail a train step
            logging.debug("telemetry: jaxpr FLOP trace failed (%s); "
                          "trying compiled cost_analysis", e)
            self.flops_per_step = self._compiled_flops(jitted, args)
        if self.flops_per_step:
            _hub().gauge("model_flops_per_step", self.flops_per_step)
        return self.flops_per_step

    @staticmethod
    def _compiled_flops(jitted, args):
        try:
            cost = jitted.lower(*args).compile().cost_analysis()
            flops = float(cost.get("flops", 0.0))
            return flops or None
        except Exception:
            return None

    # -- epoch reporting ------------------------------------------------------
    def epoch_report(self, epoch, steps, wall_seconds, *, compile_seconds=0.0,
                    data_wait_seconds=0.0, skipped_steps=0, step_retries=0,
                    checkpoint_seconds=0.0, resize_seconds=0.0,
                    profile_seconds=0.0, logger=None):
        """Compute + log + export the epoch's MFU and goodput lines.

        Badput buckets (non-overlapping slices of ``wall_seconds``):
        compile (XLA), data stalls, checkpoint flushes, elastic resizes
        (quiesce + reshard + replan + rewarm downtime plus the aborted
        partial attempt the resize threw away), profile capture windows
        (the device-time profiler's bounded traces — observation is not
        throughput), and wasted steps — retried dispatches plus
        non-finite skipped steps, each costed at the epoch's mean step
        time. Returns the report dict."""
        logger = logger or logging
        h = _hub()
        steps = max(int(steps), 0)
        wall = max(float(wall_seconds), 1e-9)
        mean_step = wall / steps if steps else 0.0
        wasted_steps = int(skipped_steps) + int(step_retries)
        badput = {
            "compile": min(float(compile_seconds), wall),
            "data_wait": min(float(data_wait_seconds), wall),
            "checkpoint": min(float(checkpoint_seconds), wall),
            "resize": min(float(resize_seconds), wall),
            "profile": min(float(profile_seconds), wall),
            "wasted_steps": min(wasted_steps * mean_step, wall),
        }
        bad_total = min(sum(badput.values()), wall)
        goodput = 100.0 * (wall - bad_total) / wall
        report = {"epoch": int(epoch), "steps": steps, "seconds": wall,
                  "mean_step_seconds": mean_step, "goodput_pct": goodput,
                  "badput": badput, "mfu_pct": None,
                  "flops_per_step": self.flops_per_step}
        if self.flops_per_step and steps and self.peak_flops:
            achieved = self.flops_per_step * steps / wall
            report["achieved_flops_per_sec"] = achieved
            report["mfu_pct"] = 100.0 * achieved / self.peak_flops
            h.gauge("mfu_pct", report["mfu_pct"])
            h.gauge("achieved_flops_per_sec", achieved)
            logger.info(
                "Epoch[%d] MFU: %.1f%% (%.3g GFLOP/step, %.2f ms/step, "
                "peak %.3g GFLOP/s over %d device(s))", epoch,
                report["mfu_pct"], self.flops_per_step / 1e9,
                mean_step * 1e3, self.peak_flops / 1e9, self.num_devices)
        else:
            logger.info("Epoch[%d] MFU: n/a (FLOPs/step or device peak "
                        "unresolved; %.2f ms/step)", epoch, mean_step * 1e3)
        h.gauge("goodput_pct", goodput)
        for reason, seconds in badput.items():
            if seconds <= 0:
                continue
            if reason == "compile":
                # deduped against any Speedometer that saw the same
                # registry delta mid-epoch (see record_compile_badput)
                from ..utils import compile as compile_mod

                record_compile_badput(
                    compile_mod.registry().snapshot()["compile_seconds"],
                    seconds, epoch=epoch)
            else:
                h.counter(f"badput_{reason}_seconds_total", seconds)
                h.emit("badput", reason=reason, seconds=seconds, epoch=epoch)
        logger.info(
            "Epoch[%d] Goodput: %.1f%% (badput: compile %.2fs, data-wait "
            "%.2fs, checkpoint %.2fs, resize %.2fs, profile %.2fs, wasted "
            "steps %d ≈ %.2fs)", epoch, goodput, badput["compile"],
            badput["data_wait"], badput["checkpoint"], badput["resize"],
            badput["profile"], wasted_steps, badput["wasted_steps"])
        h.emit("epoch_summary", **{k: v for k, v in report.items()
                                   if k != "badput"}, **{
            f"badput_{k}_seconds": v for k, v in badput.items()})
        return report
