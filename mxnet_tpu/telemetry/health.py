"""Training-health observability: in-graph per-layer statistics + streaming
anomaly detection (ISSUE 14).

The stack observes the *system* exhaustively — step phases, fleet traces,
HBM — but between loss-in and params-out the *model* was a black box: the
guard skips a NaN step without saying which layer blew up, and ``Monitor``
only reconstructs internals after the fact with an extra forward. This
module is the TPU-native ``monitor.py``: statistics computed **inside the
fused train step** (TensorFlow's in-graph summary-op stance,
arXiv:1605.08695; the reference's monitor.py workflow, arXiv:1512.01274),
no host syncs in the device path, feeding the same hub/flight/controller
machinery everything else uses.

Two halves:

  **device** — :func:`device_stats` runs in-jit at the tail of the fused
  step: per-layer gradient norm, weight norm, update:weight ratio, and
  nonfinite element counts (parameters grouped into layers by
  :func:`layer_groups`), plus the unscaled loss. The resulting pytree —
  four ``(L,)`` vectors and two scalars — threads through the step carry
  donated, exactly like the guard/error-feedback state, so the armed
  zero-recompile epoch stays green; on the compressed shard_map path the
  stats read the post-allreduce (replicated) gradients, so no extra psum
  crosses the wire. Because the stats live in the same XLA program, the
  jaxpr-audit FLOP table prices them automatically and MFU stays honest.

  **host** — :class:`HealthMonitor` is a kind-filtered hub sink over the
  ``health`` events the fit loop emits once per step (:func:`observe_
  device_stats` pulls the tiny stat vectors after the step retires).
  Streaming detectors, O(window) state, no file re-parsing:

    loss spike        | MAD z-score of the loss against a rolling window
    grad explosion    | per-layer EWMA/MAD z-score + an absolute limit
    dead layer        | update:weight ratio ~0 for K consecutive steps
    divergence drift  | fast loss EWMA above slow EWMA, sustained
    nonfinite         | any NaN/Inf element in a layer's gradients

  Each hit is a ``health_anomaly`` event — an *incident* kind, so it lands
  in the flight recorder's incident ring and a post-mortem dump names the
  layer that blew up before the guard skipped the step — plus per-layer
  ``health_*`` gauges for Prometheus and a decision-context feed for the
  fleet controller (recommend-only).

CLI: ``python -m mxnet_tpu.telemetry health run.jsonl`` renders the
per-layer table + anomaly timeline. Guide: doc/developer-guide/
telemetry.md, "Training health".
"""

from __future__ import annotations

import collections
import math
import os

from ..analysis.lockwatch import named_lock
from ..base import ENV_OFF_VALUES

__all__ = ["HealthConfig", "HealthMonitor", "layer_groups", "layer_of",
           "init_device_stats", "device_stats", "observe_device_stats",
           "aggregate_events", "ANOMALY_REASONS"]

ANOMALY_REASONS = ("nonfinite", "grad_explosion", "loss_spike",
                   "dead_layer", "divergence_drift")

# parameter-name suffixes folded into their owning layer (fc1_weight +
# fc1_bias -> layer "fc1"; BatchNorm's gamma/beta likewise)
_PARAM_SUFFIXES = ("weight", "bias", "gamma", "beta")


def layer_of(param_name: str) -> str:
    """Layer a parameter belongs to (strip the trailing role suffix)."""
    for suffix in _PARAM_SUFFIXES:
        if param_name.endswith("_" + suffix):
            return param_name[: -(len(suffix) + 1)]
    return param_name


def layer_groups(param_names):
    """Ordered ``{layer: (param names...)}`` — the fixed layer order both
    the in-jit stats engine and the host consumers index by."""
    groups: dict = {}
    for name in sorted(param_names):
        groups.setdefault(layer_of(name), []).append(name)
    return {layer: tuple(names) for layer, names in sorted(groups.items())}


class HealthConfig:
    """What ``fit(health=...)`` turns on, and the detector thresholds.

    ``every``: observe/emit stats every N steps (1 = every step).
    ``window``: rolling loss window for the MAD z-score; ``loss_z`` its
    threshold. ``grad_z``: per-layer grad-norm EWMA z-score threshold;
    ``grad_limit``: absolute grad-norm ceiling (fires with no warmup —
    catches a layer that is born exploding). ``dead_ratio``/``dead_steps``:
    update:weight ratio floor and how many consecutive sub-floor steps
    flag a dead layer. ``drift_tol``/``drift_steps``: sustained relative
    excess of the fast loss EWMA over the slow one that flags slow
    divergence. ``min_steps``: detector warmup (z-scores need a baseline).
    ``gauges``: export per-layer ``health_*`` gauges (on by default)."""

    def __init__(self, every=1, window=32, loss_z=6.0, grad_z=8.0,
                 grad_limit=1e6, dead_ratio=1e-12, dead_steps=20,
                 drift_tol=0.25, drift_steps=50, min_steps=8,
                 ewma_alpha=0.1, gauges=True):
        self.every = max(int(every), 1)
        self.window = max(int(window), 4)
        self.loss_z = float(loss_z)
        self.grad_z = float(grad_z)
        self.grad_limit = float(grad_limit)
        self.dead_ratio = float(dead_ratio)
        self.dead_steps = max(int(dead_steps), 1)
        self.drift_tol = float(drift_tol)
        self.drift_steps = max(int(drift_steps), 1)
        self.min_steps = max(int(min_steps), 2)
        self.ewma_alpha = float(ewma_alpha)
        self.gauges = bool(gauges)

    def __repr__(self):
        return (f"HealthConfig(every={self.every}, loss_z={self.loss_z}, "
                f"grad_z={self.grad_z}, grad_limit={self.grad_limit:g}, "
                f"dead_steps={self.dead_steps})")

    def key(self):
        """Hashable train-program cache-key component. The compiled
        program only depends on health being ON — ``every`` and the
        thresholds are host-side, and keying on them would orphan warmed
        programs (precompile(health=True) must serve any config)."""
        return ("health",)

    @classmethod
    def resolve(cls, value):
        """Normalize fit()'s ``health`` argument: None -> env gate
        ``MXNET_TPU_HEALTH`` (unset/falsy = off), True -> defaults,
        HealthConfig -> itself."""
        if value is None:
            raw = os.environ.get("MXNET_TPU_HEALTH", "").strip().lower()
            if not raw or raw in ENV_OFF_VALUES:
                return None
            value = True
        if value is False:
            return None
        if value is True:
            return cls()
        if isinstance(value, cls):
            return value
        raise ValueError(f"health must be bool/None/HealthConfig, "
                         f"got {type(value)}")


# -- device side (runs in-jit inside the fused train step) ---------------------

def init_device_stats(groups):
    """Zeroed health-state pytree for ``groups`` — threaded (donated)
    through the fused step like guard/EF state; fixed shapes, so the
    program signature never changes and the armed zero-recompile epoch
    stays green."""
    import jax.numpy as jnp

    n = len(groups)
    return {
        "grad_norm": jnp.zeros((n,), jnp.float32),
        "weight_norm": jnp.zeros((n,), jnp.float32),
        "update_ratio": jnp.zeros((n,), jnp.float32),
        "nonfinite": jnp.zeros((n,), jnp.int32),
        "loss": jnp.float32(0.0),
    }


def device_stats(groups, params, grads, new_params, loss):
    """Per-layer statistics, computed inside the fused step (pure,
    trace-safe; one reduction pass per parameter).

    ``grads`` are the gradients the optimizer actually consumed — on the
    compressed shard_map path the post-allreduce (replicated) values, on
    the SPMD path the partitioner-global ones — so the stats describe the
    update that really happened, on every comm/overlap/fused-Adam path.
    ``new_params`` are the post-guard-select parameters: a guard-skipped
    step reads as update_ratio 0 while its grad norms still show the
    explosion that tripped the guard."""
    import jax.numpy as jnp

    gs, ws, us, nf = [], [], [], []
    for names in groups.values():
        gsq = wsq = usq = None
        cnt = None
        for name in names:
            g32 = grads[name].astype(jnp.float32)
            w32 = params[name].astype(jnp.float32)
            d32 = new_params[name].astype(jnp.float32) - w32
            t = jnp.sum(jnp.square(g32))
            gsq = t if gsq is None else gsq + t
            t = jnp.sum(jnp.square(w32))
            wsq = t if wsq is None else wsq + t
            t = jnp.sum(jnp.square(d32))
            usq = t if usq is None else usq + t
            bad = jnp.int32(g32.size) - jnp.sum(
                jnp.isfinite(g32).astype(jnp.int32))
            cnt = bad if cnt is None else cnt + bad
        gs.append(gsq)
        ws.append(wsq)
        us.append(usq)
        nf.append(cnt)
    weight_norm = jnp.sqrt(jnp.stack(ws))
    return {
        "grad_norm": jnp.sqrt(jnp.stack(gs)),
        "weight_norm": weight_norm,
        "update_ratio": jnp.sqrt(jnp.stack(us)) / (weight_norm + 1e-12),
        "nonfinite": jnp.stack(nf).astype(jnp.int32),
        "loss": loss.astype(jnp.float32),
    }


# -- host side -----------------------------------------------------------------

def stats_to_host(groups, hstate):
    """One transfer of the tiny stat vectors -> plain python structure
    (JSON-ready). The fused step retired before this runs (the carry is
    about to be donated back in), so the pull copies ready buffers."""
    import jax
    import numpy as np

    host = jax.device_get(hstate)
    layers = {}
    for i, layer in enumerate(groups):
        layers[layer] = {
            "grad_norm": float(host["grad_norm"][i]),
            "weight_norm": float(host["weight_norm"][i]),
            "update_ratio": float(host["update_ratio"][i]),
            "nonfinite": int(host["nonfinite"][i]),
        }
    loss = float(host["loss"])
    finite = bool(np.isfinite(loss)) and all(
        v["nonfinite"] == 0 and math.isfinite(v["grad_norm"])
        for v in layers.values())
    return layers, loss, finite


def observe_device_stats(groups, hstate, epoch, step):
    """Pull one step's device stats and emit the ``health`` event (the
    stream :class:`HealthMonitor` consumes as a hub sink). Returns
    ``(event, finite)`` — the fit loop uses ``finite`` to place its
    guard-skip step event AFTER any anomaly this emit produced, so the
    incident ring reads cause before effect."""
    from . import emit

    layers, loss, finite = stats_to_host(groups, hstate)
    event = emit("health", epoch=int(epoch), step=int(step), loss=loss,
                 finite=finite, stats=layers)
    return event, finite


def aggregate_events(events):
    """Per-layer aggregate over exported ``health``/``health_anomaly``
    events — the one table builder behind the ``telemetry health``
    CLI: last + max gradient norm, last
    weight norm and update:weight ratio, summed nonfinite elements, and
    the anomaly count attributed to each layer."""
    def _fresh():
        return {"grad_norm": 0.0, "max_grad_norm": 0.0, "weight_norm": 0.0,
                "update_ratio": 0.0, "nonfinite": 0, "anomalies": 0}

    layers: dict = {}
    for e in events:
        kind = e.get("kind")
        if kind == "health":
            for layer, row in (e.get("stats") or {}).items():
                agg = layers.setdefault(layer, _fresh())
                agg["grad_norm"] = float(row.get("grad_norm", 0.0))
                agg["max_grad_norm"] = max(agg["max_grad_norm"],
                                           float(row.get("grad_norm", 0.0)))
                agg["weight_norm"] = float(row.get("weight_norm", 0.0))
                agg["update_ratio"] = float(row.get("update_ratio", 0.0))
                agg["nonfinite"] += int(row.get("nonfinite", 0))
        elif kind == "health_anomaly" and e.get("layer") is not None:
            layers.setdefault(e["layer"], _fresh())["anomalies"] += 1
    return layers


class _LayerTrack:
    __slots__ = ("ewma", "mad", "n", "dead_run")

    def __init__(self):
        self.ewma = None
        self.mad = 0.0
        self.n = 0
        self.dead_run = 0


class HealthMonitor:
    """Streaming anomaly detection over ``health`` events.

    Attach with :meth:`attach` (a kind-filtered hub sink — each health
    event costs one lock + O(layers) float math at emit time; no file
    parsing, no device access). Detection runs synchronously inside the
    emitting ``telemetry.emit("health", ...)`` call, so a ``health_
    anomaly`` incident always lands in the flight ring BEFORE whatever
    the emitter does next (the ordering the guard-skip post-mortem
    contract relies on). Thread-safe; the fleet controller reads
    :meth:`report`/:meth:`blamed_layer` from its own thread."""

    def __init__(self, config=None):
        self.cfg = config or HealthConfig()
        self._lock = named_lock("telemetry.health.HealthMonitor")
        self._layers: dict = {}          # layer -> _LayerTrack
        self._loss_ring = collections.deque(maxlen=self.cfg.window)
        self._loss_fast = None
        self._loss_slow = None
        self._drift_run = 0
        self._steps = 0
        self._last_stats = {}
        self._last_loss = None
        self._last_step = None
        self.anomalies = []              # bounded recent-anomaly list
        self._anomaly_marks = []         # aligned: _steps count at record
        self._anomaly_counts = collections.Counter()  # (layer, reason)
        self._attached = None

    # -- hub sink protocol -----------------------------------------------------
    def write_event(self, event):
        if event.get("kind") != "health":
            return
        self.observe(event)

    def feed(self, events):
        """Manual ingestion (tests / bench replay of an exported stream)."""
        for e in events:
            self.write_event(e)

    def attach(self, h=None):
        """Register as a kind-filtered sink (default: the process hub).
        Idempotent per hub; attaching to a DIFFERENT hub detaches from
        the previous one first (a monitor must never feed two hubs).
        Returns self."""
        from .hub import hub as _hub

        h = h or _hub()
        if self._attached is h:
            return self
        if self._attached is not None:
            self.detach()
        if not h.has_sink(self):
            h.add_sink(self, kinds=("health",))
        self._attached = h
        return self

    def detach(self):
        if self._attached is not None:
            self._attached.remove_sink(self)
            self._attached = None

    # -- detection -------------------------------------------------------------
    def observe(self, event):
        cfg = self.cfg
        stats = event.get("stats") or {}
        loss = event.get("loss")
        epoch = int(event.get("epoch", 0))
        step = int(event.get("step", 0))
        found = []
        with self._lock:
            self._steps += 1
            n_seen = self._steps
            self._last_stats = stats
            self._last_loss = loss
            self._last_step = (epoch, step)

            # loss spike: MAD z-score against the rolling window
            if loss is not None and math.isfinite(loss):
                ring = self._loss_ring
                if len(ring) >= cfg.min_steps:
                    vals = sorted(ring)
                    med = vals[len(vals) // 2]
                    mad = sorted(abs(v - med) for v in vals)[len(vals) // 2]
                    z = abs(loss - med) / (1.4826 * mad + 1e-12)
                    if z > cfg.loss_z:
                        found.append(("loss_spike", None, loss, cfg.loss_z,
                                      {"zscore": round(z, 2)}))
                ring.append(loss)
                # slow divergence drift: fast EWMA sustained above slow
                a_f, a_s = cfg.ewma_alpha, cfg.ewma_alpha / 8.0
                self._loss_fast = loss if self._loss_fast is None else \
                    (1 - a_f) * self._loss_fast + a_f * loss
                self._loss_slow = loss if self._loss_slow is None else \
                    (1 - a_s) * self._loss_slow + a_s * loss
                drifting = n_seen > cfg.min_steps and \
                    self._loss_fast > self._loss_slow * (1 + cfg.drift_tol)
                self._drift_run = self._drift_run + 1 if drifting else 0
                if self._drift_run == cfg.drift_steps:
                    found.append((
                        "divergence_drift", None, self._loss_fast,
                        cfg.drift_tol,
                        {"ewma_slow": round(self._loss_slow, 6),
                         "run_steps": self._drift_run}))
                    self._drift_run = 0

            step_finite = bool(event.get("finite", True))
            for layer, row in stats.items():
                track = self._layers.get(layer)
                if track is None:
                    track = self._layers[layer] = _LayerTrack()
                nonfinite = int(row.get("nonfinite", 0))
                gnorm = float(row.get("grad_norm", 0.0))
                ratio = float(row.get("update_ratio", 0.0))
                if nonfinite > 0 or not math.isfinite(gnorm):
                    found.append(("nonfinite", layer, nonfinite, 0,
                                  {"grad_norm": gnorm}))
                    continue  # a NaN norm must not poison the EWMA
                anomalous = False
                if gnorm > cfg.grad_limit:
                    found.append(("grad_explosion", layer, gnorm,
                                  cfg.grad_limit, {"absolute": True}))
                    anomalous = True
                elif track.n >= cfg.min_steps:
                    z = (gnorm - track.ewma) / (1.4826 * track.mad + 1e-12)
                    if z > cfg.grad_z:
                        found.append(("grad_explosion", layer, gnorm,
                                      cfg.grad_z, {"zscore": round(z, 2),
                                                   "ewma": track.ewma}))
                        anomalous = True
                if not anomalous:
                    # anomalous samples stay out of the baseline: repeated
                    # spikes must not normalize themselves away
                    a = cfg.ewma_alpha
                    if track.ewma is None:
                        track.ewma = gnorm
                    else:
                        track.mad = (1 - a) * track.mad + \
                            a * abs(gnorm - track.ewma)
                        track.ewma = (1 - a) * track.ewma + a * gnorm
                    track.n += 1
                # dead layer: ratio ~0 across consecutive OBSERVED finite
                # steps (guard-skipped steps write ratio 0 by construction
                # and must not count toward death)
                if step_finite and ratio < cfg.dead_ratio:
                    track.dead_run += 1
                    if track.dead_run == cfg.dead_steps:
                        found.append(("dead_layer", layer, ratio,
                                      cfg.dead_ratio,
                                      {"steps": cfg.dead_steps}))
                        track.dead_run = 0
                elif step_finite:
                    track.dead_run = 0
            for reason, layer, _v, _t, _x in found:
                self._anomaly_counts[(layer, reason)] += 1
        self._publish(event, stats, loss, found)
        return found

    def _publish(self, event, stats, loss, found):
        """Gauges + anomaly events OUTSIDE the detector lock (emit calls
        sinks; re-entering the hub while holding our lock would invert
        lock order against concurrent readers)."""
        from . import counter, emit, gauge

        cfg = self.cfg
        if cfg.gauges:
            if loss is not None:
                gauge("health_loss", loss)
            for layer, row in stats.items():
                gauge("health_grad_norm", row.get("grad_norm", 0.0),
                      layer=layer)
                gauge("health_weight_norm", row.get("weight_norm", 0.0),
                      layer=layer)
                gauge("health_update_ratio", row.get("update_ratio", 0.0),
                      layer=layer)
                gauge("health_nonfinite", row.get("nonfinite", 0),
                      layer=layer)
        for reason, layer, value, threshold, extra in found:
            counter("health_anomalies_total", reason=reason)
            rec = emit("health_anomaly", reason=reason, layer=layer,
                       epoch=event.get("epoch", 0),
                       step=event.get("step", 0),
                       value=value, threshold=threshold, **extra)
            with self._lock:
                self.anomalies.append(rec)
                # age is counted in OBSERVED steps (monotonic across
                # epochs — event step numbers reset per epoch and cannot
                # express "N healthy steps ago")
                self._anomaly_marks.append(self._steps)
                del self.anomalies[:-256]
                del self._anomaly_marks[:-256]

    # -- queries ---------------------------------------------------------------
    @property
    def steps_seen(self):
        with self._lock:
            return self._steps

    def blamed_layer(self, within_steps=None):
        """(layer, reason) of the most recent layer-attributed anomaly —
        the fleet controller's decision context — or None. ``within_
        steps`` bounds how stale a blame may be, counted in OBSERVED
        steps (monotonic across epochs; default: 2 windows)."""
        within = (2 * self.cfg.window if within_steps is None
                  else int(within_steps))
        with self._lock:
            for rec, mark in zip(reversed(self.anomalies),
                                 reversed(self._anomaly_marks)):
                if rec.get("layer") is None:
                    continue
                if self._steps - mark > within:
                    return None  # newest blame already aged out
                return rec["layer"], rec["reason"]
        return None

    def report(self):
        """Point-in-time health summary: last per-layer stats, per-layer
        anomaly counts, recent anomalies, steps observed."""
        with self._lock:
            layers = {}
            for layer, row in self._last_stats.items():
                counts = {r: c for (l, r), c in self._anomaly_counts.items()
                          if l == layer}
                layers[layer] = {**row, "anomalies": counts}
            return {
                "steps": self._steps,
                "loss": self._last_loss,
                "layers": layers,
                "anomalies": list(self.anomalies[-32:]),
                "anomaly_counts": {f"{l or '-'}/{r}": c for (l, r), c
                                   in sorted(self._anomaly_counts.items())},
            }

    def clear(self):
        with self._lock:
            self._layers.clear()
            self._loss_ring.clear()
            self._loss_fast = self._loss_slow = None
            self._drift_run = 0
            self._steps = 0
            self._last_stats = {}
            self._last_loss = None
            self._last_step = None
            self.anomalies = []
            self._anomaly_marks = []
            self._anomaly_counts.clear()
