"""Operator registry and the OpProp contract.

Reference counterpart: include/mxnet/operator.h — ``OperatorProperty``
(shape/arg metadata) + ``Operator`` (Forward/Backward kernels) + the
``MXNET_REGISTER_OP_PROPERTY`` registry, with op configs declared through
``dmlc::Parameter`` reflection (single source of truth for docs/signatures).

TPU-native redesign: one class per op. The kernel is a *pure function*
``fwd(ins, aux, is_train, rng) -> (outs, new_aux)`` in jax.numpy/lax —
traceable, differentiable, fusable by XLA. There is no Backward method:
autodiff is ``jax.vjp`` of the traced graph, and ops whose reference
Backward is *not* the true derivative (loss heads) express that via
``jax.custom_vjp`` inside their forward. ``DeclareBackwardDependency`` /
inplace metadata disappear into XLA's buffer assignment; resource requests
(workspace/RNG) become explicit ``rng`` arguments.

Param declaration mirrors dmlc::Parameter: a class-level ``params`` dict of
``name -> (type, default_or_REQUIRED, doc)``; values are validated and
normalized at construction, and docstrings are auto-generated from it
(reference: c_api.cc:378-391 doc export).
"""

from __future__ import annotations

from ..base import MXNetError, Registry
from ..params import REQUIRED, Range, TupleParam, apply_params, autodoc

__all__ = ["OpProp", "OPS", "register_op", "REQUIRED", "Range", "TupleParam"]

OPS = Registry("operator")

# ``jax.ad_checkpoint.checkpoint_name`` under which an operator marks a value
# that a recomputation segment of the executor keeps rather than recomputes
REMAT_KEEP = "mxnet_tpu.remat_keep"


class OpProp:
    """Base class for operator properties (metadata + pure-fn kernel).

    Subclasses define:
      params       : dict name -> (type, default|REQUIRED, doc)
      list_arguments / list_outputs / list_auxiliary_states
      infer_shape(in_shapes) -> (in_shapes, out_shapes, aux_shapes)
      fwd(ins, aux, is_train, rng) -> (outs, new_aux)
      need_rng     : True if fwd consumes randomness in training mode
      epoch_record(before, after) -> (span name, attrs)   (optional)
                   an operator whose auxiliary states are worth a line an
                   epoch defines it: ``fit`` calls it once an epoch, after
                   the write-back, with the node's auxiliary states as
                   numpy arrays (``list_auxiliary_states`` order) as they
                   were at the previous call (at ``fit``'s start for the
                   first) and as they are now, and emits one zero-length
                   telemetry record of that name with ``epoch``, ``node``
                   and the attrs. ``fit`` knows no operator by name.
      argument_major_to_minor() -> {argument name: axes, major to minor}
                   the order in which the operator's arithmetic reads a
                   learnable argument, where that is not the declared
                   (row-major) one. The train step then keeps that leaf and
                   its optimizer state in this order on the device, from
                   placement to write-back, and no step copies it there and
                   back (``FeedForward``'s stored order); the declared
                   shape is what ``arg_params``, checkpoints and
                   ``infer_shape`` go on showing.
    """

    params: dict = {}
    need_rng = False
    # Non-None => executor treats output[0] as a loss head whose gradient is
    # injected by the op's custom_vjp (cotangent ignored), matching the
    # reference's loss-op Backward semantics.
    is_loss = False

    def __init__(self, **kwargs):
        self.attr = apply_params(type(self).__name__, type(self).params, kwargs)

    def __getattr__(self, item):
        try:
            return self.__dict__["attr"][item]
        except KeyError:
            raise AttributeError(item) from None

    # -- metadata -------------------------------------------------------------
    @property
    def name(self):
        return type(self).op_name

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def list_auxiliary_states(self):
        return []

    def num_inputs(self):
        return len(self.list_arguments())

    def num_outputs(self):
        return len(self.list_outputs())

    # -- shape inference ------------------------------------------------------
    def infer_shape(self, in_shapes):
        """Complete partial input shapes; return (in, out, aux) shape lists.

        ``in_shapes`` entries are tuples or None (unknown). The default
        requires the first input and propagates it elementwise.
        """
        d = self._known(in_shapes, 0)
        return [d] * len(in_shapes), [d], []

    def argument_major_to_minor(self):
        return {}

    def _known(self, in_shapes, idx):
        s = in_shapes[idx]
        if s is None:
            raise MXNetError(
                f"{self.name}: shape of input '{self.list_arguments()[idx]}' unknown"
            )
        return tuple(s)

    # -- dtype inference ------------------------------------------------------
    def infer_dtype(self, in_dtypes):
        """Complete partial input dtypes; return (in, out, aux) dtype lists.

        Mirrors ``infer_shape`` (reference: OperatorProperty::InferType).
        The default propagates the first known input dtype everywhere and
        requires the known inputs to agree — except loss-head ``label``
        inputs, whose dtype is independent of the data path (int class ids
        against float logits is the normal case). Ops with genuinely
        heterogeneous inputs (Embedding: int ids + float table) override.
        """
        import numpy as np

        args = self.list_arguments()
        known = [(i, np.dtype(d)) for i, d in enumerate(in_dtypes)
                 if d is not None]
        if not known:
            raise MXNetError(f"{self.name}: no input dtype known")
        d = known[0][1]
        for i, dt in known:
            if self.is_loss and args[i] == "label":
                continue
            if dt != d:
                raise MXNetError(
                    f"{self.name}: input '{args[i]}' has dtype {dt} but "
                    f"'{args[known[0][0]]}' has dtype {d}")
        completed = [
            (np.dtype(in_dtypes[i]) if in_dtypes[i] is not None else d)
            for i in range(len(in_dtypes))
        ]
        return (completed, [d] * self.num_outputs(),
                [d] * len(self.list_auxiliary_states()))

    # -- kernel ---------------------------------------------------------------
    def fwd(self, ins, aux, is_train, rng):
        raise NotImplementedError

    # loss-mask support (utils/compile.PadPolicy): loss heads that can zero
    # padded rows' injected gradients set ``supports_loss_mask = True`` and
    # implement ``fwd_masked`` — forward identical to ``fwd``, backward
    # multiplies the injected per-row gradient by ``mask`` (shape (batch,)).
    supports_loss_mask = False

    def fwd_masked(self, ins, aux, is_train, rng, mask):
        raise MXNetError(
            f"{type(self).__name__} does not support loss masking; "
            "PadPolicy needs a mask-capable loss head (see ops/loss.py)")

    def loss_value(self, out, label, mask=None):
        """The scalar training loss this head's injected gradient descends
        (trace-safe; telemetry.health's loss stream). Loss heads OUTPUT
        predictions and inject their gradient through a custom VJP — the
        seed-ones cotangent scalar the fused step reduces is a gradient
        seed, CONSTANT for softmax heads — so observability needs this
        explicit hook. None (the default) = this op cannot price its loss;
        the health stream falls back to the seed scalar."""
        del out, label, mask
        return None

    def serialize_params(self) -> dict:
        """JSON-able param dict for Symbol save/load."""
        return {k: (list(v) if isinstance(v, tuple) else v) for k, v in self.attr.items()}

    def __repr__(self):
        return f"{type(self).__name__}({self.attr})"


def register_op(op_name, aliases=()):
    """Register an OpProp subclass under ``op_name`` (+ optional aliases)."""

    def _reg(cls):
        cls.op_name = op_name
        cls.op_aliases = tuple(aliases)
        OPS.register(op_name)(cls)
        for alias in aliases:
            OPS._entries[alias.lower()] = cls
        autodoc(cls)
        return cls

    return _reg
