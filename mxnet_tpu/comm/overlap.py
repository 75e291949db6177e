"""Comm/compute overlap scheduler: per-bucket quantized sync inside the jit.

PR 4's quantized allreduce fires as ONE fused bucket after the whole
backward pass, so a step pays compute + comm serially. This module splits
the gradient pytree into GradBucketer-style size-capped slabs and launches
each bucket's quantized reduce-scatter/all-gather pair as its own
independent collective, scheduled in **reverse-topological parameter
order** (last layers first — the order backward actually produces
gradients, arXiv:1802.06949's collective-in-the-DAG idea taken to XLA):

    backward:   ... <- layer2 grads <- layer3 grads <- layer4 grads
    wire:              bucket{4,3}~~~~~  bucket{2}~~~~~  bucket{1}~~~~~
                       (each pair depends only on ITS bucket's grads)

Nothing sequences bucket k's collectives against bucket k+1's compute —
the dataflow graph ties each reduce-scatter only to the gradients it
moves, so XLA's scheduler is free to interleave bucket k's wire time with
the rest of backward. The ``optimization_barrier`` pinning inside each
exchange (comm/allreduce.py) protects the wire dtype from convert
commuting (mxlint MX308); it does NOT create cross-bucket ordering.

Error feedback generalizes to **per-bucket residuals**: one
``(axis_size, Lp_b)`` row-sharded ledger per bucket, checkpointed like
optimizer state and keyed on the plan layout so a bucket-plan change
(different cap, params, compression, or mesh) invalidates them safely
instead of silently cross-injecting stale error (see
``residuals_match_plan`` / ``OverlapPlan.layout_key``).

Entry points: ``FeedForward.fit(compression=..., overlap=...)``,
``parallel.make_data_parallel_step(compression=..., overlap=...)``, and
the kvstore stale-sync mode (``AsyncKVStore.push_pull_stale`` — bucket
pushes lag one step behind compute, ps-lite heritage, arXiv:2506.17615
quantization on the wire either way). Wire accounting:
``comm.stats.overlap_plan`` (per-bucket closed-form plans that sum
exactly to the fused plan). Guide: doc/developer-guide/comm.md.
"""

from __future__ import annotations

import hashlib
import os

from ..base import MXNetError
from .allreduce import (compressed_allreduce, error_feedback_allreduce,
                        init_error_feedback, padded_flat_size)
from .bucketing import DEFAULT_BUCKET_BYTES, GradBucketer
from .compression import CompressionSpec

__all__ = ["OverlapConfig", "OverlapPlan", "plan_overlap",
           "reverse_topo_param_order", "overlap_allreduce",
           "init_overlap_residuals", "residuals_match_plan",
           "fused_layout_key", "overlap_efficiency"]

_OFF_VALUES = ("", "0", "off", "false", "no", "none")
_ON_VALUES = ("1", "on", "true", "yes")


class OverlapConfig:
    """What the ``overlap=`` knob resolved to.

    ``bucket_bytes``: f32 byte cap per gradient slab (the DDP-style 4 MB
    default). Smaller buckets start wiring earlier but pay more per-bucket
    padding + collective launch overhead; the plan arithmetic
    (``stats.overlap_plan``) prices the padding exactly.
    """

    def __init__(self, bucket_bytes=DEFAULT_BUCKET_BYTES):
        self.bucket_bytes = int(bucket_bytes)
        if self.bucket_bytes <= 0:
            raise MXNetError("overlap bucket_bytes must be positive")

    def __repr__(self):
        return f"OverlapConfig(bucket_bytes={self.bucket_bytes})"

    def key(self):
        """Hashable identity (train-program cache key component)."""
        return ("overlap", self.bucket_bytes)

    @classmethod
    def resolve(cls, value):
        """Normalize a user-facing ``overlap`` argument.

        None -> env gate ``MXNET_TPU_COMM_OVERLAP`` (unset/falsy = off,
        truthy = default 4 MB buckets, an integer = the bucket byte cap);
        True -> default; an int -> that byte cap; a config passes through.
        """
        if value is None:
            raw = os.environ.get("MXNET_TPU_COMM_OVERLAP", "").strip().lower()
            if raw in _OFF_VALUES:
                return None
            if raw in _ON_VALUES:
                return cls()
            value = raw
        if value is False:
            return None
        if value is True:
            return cls()
        if isinstance(value, cls):
            return value
        try:
            return cls(int(value))
        except (TypeError, ValueError):
            raise MXNetError(
                f"overlap= must be True/False, a bucket byte cap, or an "
                f"OverlapConfig; got {value!r}") from None


def reverse_topo_param_order(symbol, param_names):
    """Order ``param_names`` the way backward produces their gradients.

    Backward replays the forward graph in reverse, and a parameter's
    gradient is complete once its topologically-EARLIEST consumer's
    backward op has run — so sorting by first-consumer topo index,
    descending, puts last layers first: exactly the order in which each
    bucket's reduce-scatter can start while earlier layers' backward is
    still computing. Ties (a layer's weight and bias) keep the caller's
    relative order; names the graph never consumes go last.
    """
    wanted = set(param_names)
    first_use = {}
    for idx, node in enumerate(symbol._topo()):
        if node.is_variable:
            continue
        for src, _ in node.inputs:
            if src.is_variable and src.name in wanted:
                cur = first_use.get(src.name)
                if cur is None or idx < cur:
                    first_use[src.name] = idx
    ranked = sorted((n for n in param_names if n in first_use),
                    key=lambda n: -first_use[n])
    return ranked + [n for n in param_names if n not in first_use]


class OverlapPlan:
    """Static per-bucket schedule: which parameters fuse into which slab,
    in schedule (reverse-topological) order, plus the padded per-bucket
    lengths every consumer needs — the traced sync, the residual ledgers,
    the closed-form wire plan, and the checkpoint layout key all derive
    from this one object, so they cannot drift."""

    def __init__(self, spec, axis_size, buckets):
        self.spec = spec
        self.axis_size = int(axis_size)
        # [{"name", "keys", "shapes", "size", "padded"}] in schedule order
        self.buckets = buckets

    @property
    def num_buckets(self):
        return len(self.buckets)

    def bucket_elems(self):
        """``[(bucket_name, num_elements), ...]`` in schedule order."""
        return [(b["name"], b["size"]) for b in self.buckets]

    def padded_sizes(self):
        """``{bucket_name: padded_length}`` (residual row lengths)."""
        return {b["name"]: b["padded"] for b in self.buckets}

    def param_keys(self):
        return [k for b in self.buckets for k in b["keys"]]

    def layout_key(self) -> str:
        """Stable identity of (schedule, shapes, spec, mesh extent) — the
        checkpoint key that decides whether saved per-bucket residuals are
        still meaningful (a residual only compensates the slab it was
        computed against)."""
        desc = (self.spec.key(), self.axis_size,
                [(b["name"], b["keys"], b["shapes"]) for b in self.buckets])
        return "overlap:" + hashlib.sha1(repr(desc).encode()).hexdigest()[:16]

    def wire_plan(self) -> dict:
        """Exact per-bucket comm plan (see :func:`stats.overlap_plan`)."""
        from .stats import overlap_plan

        return overlap_plan(self.bucket_elems(), self.axis_size, self.spec)

    def replan(self, axis_size) -> "OverlapPlan":
        """The same parameter set, schedule, and compression on a
        different axis size (elastic resize). Bucket membership and order
        are topology-independent — only the per-bucket padded lengths
        (reduce-scatter rows) and the layout key change, which is exactly
        why a resize invalidates checkpointed residuals: the new plan's
        ``layout_key()`` differs and ``residuals_match_plan`` rejects the
        old ``(old_axis, Lp)`` ledgers."""
        axis_size = int(axis_size)
        buckets = [{**b, "padded": padded_flat_size(b["size"], self.spec,
                                                    axis_size)}
                   for b in self.buckets]
        return OverlapPlan(self.spec, axis_size, buckets)

    def __repr__(self):
        return (f"OverlapPlan(mode={self.spec.mode!r}, "
                f"axis_size={self.axis_size}, buckets={self.num_buckets})")


def plan_overlap(shapes, compression, axis_size,
                 max_bytes=DEFAULT_BUCKET_BYTES, symbol=None):
    """Build the per-bucket schedule for a parameter set.

    ``shapes``: ``{param_name: shape}`` (or ``[(name, shape), ...]``).
    With ``symbol`` the schedule order comes from the graph
    (:func:`reverse_topo_param_order`); without one, names are sorted and
    reversed — a canonical order both sides of a traced boundary rebuild
    identically from the gradient tree alone (jax dict trees iterate
    sorted), at the cost of only approximating the backward order.
    """
    spec = CompressionSpec.resolve(compression)
    if spec is None:
        raise MXNetError("plan_overlap needs an active compression mode "
                         "(the overlapped schedule pipelines the quantized "
                         "per-bucket sync)")
    axis_size = int(axis_size)
    items = list(shapes.items()) if isinstance(shapes, dict) \
        else [(k, tuple(s)) for k, s in shapes]
    by_name = {k: tuple(int(d) for d in s) for k, s in items}
    if symbol is not None:
        ordered = reverse_topo_param_order(symbol, [k for k, _ in items])
    else:
        ordered = sorted(by_name)[::-1]
    bucketer = GradBucketer([(n, by_name[n]) for n in ordered],
                            max_bytes=max_bytes)
    buckets = [{"name": b["name"], "keys": list(b["keys"]),
                "shapes": list(b["shapes"]), "size": b["size"],
                "padded": padded_flat_size(b["size"], spec, axis_size)}
               for b in bucketer.buckets]
    return OverlapPlan(spec, axis_size, buckets)


def overlap_allreduce(tree, residuals, plan, axis_name="dp", average=False,
                      kernels=None):
    """Sync a gradient pytree as independent per-bucket collective pairs
    (call inside shard_map, like :func:`compressed_allreduce`).

    Buckets go on the wire in ``plan``'s schedule order, but nothing in
    the emitted graph sequences them against each other — each pair
    depends only on its own bucket's gradients, which is what lets XLA
    hide bucket k's wire time under the rest of backward.

    ``residuals``: ``{bucket_name: (1, Lp_b)}`` — this device's slices of
    the carried ``(axis_size, Lp_b)`` error-feedback state
    (:func:`init_overlap_residuals`, ``P(axis)``-sharded), or None for
    modes without feedback. Returns ``(synced_tree, new_residuals)``.
    ``kernels`` (a CommKernelConfig) routes each bucket's quantize
    stages through the fused Pallas kernels, same as the fused path.
    """
    missing = [k for k in plan.param_keys() if k not in tree]
    extra = [k for k in tree if k not in set(plan.param_keys())]
    if missing or extra:
        raise MXNetError(
            f"overlap_allreduce: gradient keys do not match the plan "
            f"(missing={missing[:3]}, unplanned={extra[:3]}); rebuild the "
            f"plan with plan_overlap for this parameter set")
    use_ef = plan.spec.error_feedback and residuals is not None
    out = {}
    new_res = dict(residuals) if use_ef else residuals
    for b in plan.buckets:
        sub = {k: tree[k] for k in b["keys"]}
        if use_ef:
            synced, r = error_feedback_allreduce(
                sub, residuals[b["name"]], plan.spec, axis_name=axis_name,
                axis_size=plan.axis_size, average=average, kernels=kernels)
            new_res[b["name"]] = r
        else:
            synced = compressed_allreduce(
                sub, plan.spec, axis_name=axis_name,
                axis_size=plan.axis_size, average=average, kernels=kernels)
        out.update(synced)
    return out, new_res


def init_overlap_residuals(plan, dtype=None):
    """Zero per-bucket error-feedback state for ``plan`` — a
    ``{bucket_name: (axis_size, Lp_b)}`` dict to shard ``P(axis)`` and
    thread through the step carry — or None when the mode needs none."""
    if not plan.spec.error_feedback:
        return None
    return {b["name"]: init_error_feedback(b["size"], plan.spec,
                                           plan.axis_size, dtype)
            for b in plan.buckets}


def residuals_match_plan(residuals, plan) -> bool:
    """Do checkpointed residual arrays still describe ``plan``'s buckets?
    Shape-level check on top of the layout key: names AND (axis_size, Lp)
    per bucket must agree before a resumed run may reuse them."""
    if not plan.spec.error_feedback:
        return residuals is None
    if not isinstance(residuals, dict):
        return False
    expected = {b["name"]: (plan.axis_size, b["padded"])
                for b in plan.buckets}
    if set(residuals) != set(expected):
        return False
    return all(tuple(int(d) for d in residuals[n].shape) == shape
               for n, shape in expected.items())


def overlap_efficiency(step_seconds, compute_seconds, comm_seconds) -> float:
    """The overlap-efficiency gauge: how much of the smaller of
    (compute, comm) the schedule actually hid.

        1 - (step - max(compute, comm)) / min(compute, comm)

    1.0 = perfect pipelining (step == max(compute, comm): the smaller
    side rides entirely under the larger); 0.0 = fully serial (step ==
    compute + comm); negative = the schedule ADDED time beyond serial.
    Capped at 1.0: more than min(compute, comm) cannot be hidden, so a
    raw value above 1 is measurement skew (e.g. comm that also rode
    under host work outside the measured compute), not extra credit.
    Published as the hub gauge ``comm_overlap_efficiency`` (fit's
    stale-sync epoch accounting). Returns 0.0 when either side is ~zero
    — nothing to hide, nothing hidden."""
    lo = min(float(compute_seconds), float(comm_seconds))
    if lo <= 0.0:
        return 0.0
    return min(1.0, 1.0 - (float(step_seconds)
                           - max(float(compute_seconds),
                                 float(comm_seconds))) / lo)


def fused_layout_key(num_elements, spec, axis_size) -> str:
    """Layout identity for the single fused-bucket residual (the
    non-overlap path), so its checkpoint entry gets the same
    change-detection as the per-bucket ledgers."""
    lp = padded_flat_size(num_elements, spec, int(axis_size))
    return (f"fused:{spec.mode}:{spec.threshold}:{spec.chunk}:"
            f"{int(axis_size)}:{int(num_elements)}:{lp}")
