"""Loss-head operators with reference-faithful injected gradients.

Reference counterparts: src/operator/softmax_output-inl.h and
regression_output-inl.h. In the reference these ops' Backward does NOT
compute the derivative of their forward output — it injects the loss
gradient directly (softmax-cross-entropy: p - onehot(label); regression:
pred - label) and ignores any incoming out_grad. We reproduce that contract
with ``jax.custom_vjp`` whose backward rule discards the cotangent, so
``Executor.backward()`` (which seeds ones) and ``jax.grad`` of a sum over
outputs both yield byte-identical gradients to the reference semantics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..base import MXNetError
from .registry import OpProp, Range, REQUIRED, register_op


def _softmax(x, axis):
    return jax.nn.softmax(x.astype(jnp.float32), axis=axis).astype(x.dtype)


def _cross_entropy(out, label):
    """``-log p[label]`` a row of an already-computed softmax ``out``
    ((batch, C) or multi-output (batch, C, ...), label (batch, ...)),
    summed over a row's positions: (batch,) float32."""
    # idx[:, None] expands the class axis for both shapes
    p = out.astype(jnp.float32)
    idx = label.astype(jnp.int32)
    nll = -jnp.log(jnp.take_along_axis(p, idx[:, None], axis=1)[:, 0]
                   + 1e-12)
    return nll.reshape(nll.shape[0], -1).sum(axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _softmax_output(data, label, grad_scale, multi_output):
    axis = 1 if (multi_output or data.ndim > 2) else -1
    return _softmax(data, axis)


def _softmax_output_fwd(data, label, grad_scale, multi_output):
    out = _softmax_output(data, label, grad_scale, multi_output)
    return out, (out, label)


def _softmax_output_bwd(grad_scale, multi_output, res, g):
    del g  # reference semantics: out_grad to a loss head is ignored
    out, label = res
    axis = 1 if (multi_output or out.ndim > 2) else -1
    num_classes = out.shape[axis]
    onehot = jax.nn.one_hot(
        label.astype(jnp.int32), num_classes, axis=axis, dtype=jnp.float32
    )
    d_data = (out.astype(jnp.float32) - onehot) * grad_scale
    return d_data.astype(out.dtype), jnp.zeros_like(label)


_softmax_output.defvjp(_softmax_output_fwd, _softmax_output_bwd)


def _row_mask(mask, ndim):
    """(batch,) validity mask broadcast against a (batch, ...) gradient."""
    return mask.astype(jnp.float32).reshape(mask.shape + (1,) * (ndim - 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _softmax_output_masked(data, label, mask, grad_scale, multi_output):
    axis = 1 if (multi_output or data.ndim > 2) else -1
    return _softmax(data, axis)


def _softmax_output_masked_fwd(data, label, mask, grad_scale, multi_output):
    out = _softmax_output_masked(data, label, mask, grad_scale, multi_output)
    return out, (out, label, mask)


def _softmax_output_masked_bwd(grad_scale, multi_output, res, g):
    del g  # loss head: out_grad ignored (reference semantics)
    out, label, mask = res
    axis = 1 if (multi_output or out.ndim > 2) else -1
    num_classes = out.shape[axis]
    onehot = jax.nn.one_hot(
        label.astype(jnp.int32), num_classes, axis=axis, dtype=jnp.float32
    )
    d_data = (out.astype(jnp.float32) - onehot) * grad_scale
    # padded rows (mask 0) inject NO gradient: parameter grads of a
    # padded+masked batch equal the unpadded batch exactly (backward is
    # linear in the injected cotangent)
    d_data = d_data * _row_mask(mask, d_data.ndim)
    return (d_data.astype(out.dtype), jnp.zeros_like(label),
            jnp.zeros_like(mask))


_softmax_output_masked.defvjp(_softmax_output_masked_fwd,
                              _softmax_output_masked_bwd)


@register_op("SoftmaxOutput", aliases=["Softmax"])
class SoftmaxOutputOp(OpProp):
    """Softmax forward + cross-entropy gradient injection (reference:
    softmax_output.cc:22-27; the bare ``Softmax`` name is the deprecated
    alias the reference keeps)."""

    params = {
        "grad_scale": (float, 1.0, "multiplier applied to the injected gradient"),
        "multi_output": (bool, False, "softmax over axis 1 with per-position labels"),
    }
    is_loss = True

    def list_arguments(self):
        return ["data", "label"]

    def infer_shape(self, in_shapes):
        d = self._known(in_shapes, 0)
        if self.multi_output or len(d) > 2:
            label = (d[0],) + tuple(d[2:])
        else:
            label = (d[0],)
        return [d, label], [d], []

    def fwd(self, ins, aux, is_train, rng):
        return [_softmax_output(ins[0], ins[1], self.grad_scale, self.multi_output)], []

    supports_loss_mask = True

    def fwd_masked(self, ins, aux, is_train, rng, mask):
        return [_softmax_output_masked(ins[0], ins[1], mask,
                                       self.grad_scale, self.multi_output)], []

    def loss_value(self, out, label, mask=None):
        """Cross-entropy of the already-computed softmax output — the loss
        whose gradient this head injects (sum over valid rows, scaled like
        the injected gradient)."""
        nll = _cross_entropy(out, label)
        if mask is not None:
            nll = nll * mask
        return jnp.sum(nll) * self.grad_scale


@register_op("MaskedDiffusionOutput")
class MaskedDiffusionOutputOp(OpProp):
    """Loss head of masked (absorbing-state) diffusion within blocks, the
    training objective of a block-diffusion decoder (BD3-LM, Arriola et
    al., arXiv:2503.09573).

    ``data`` (rows, classes): logits on the noisy rows; ``label``: the
    clean ids; ``noisy``: the ids the model saw, ``mask_id`` where a
    position was masked (same shape as ``label``, its last axis whole
    blocks of ``block_length``; no gradient). Forward is the softmax, read
    like ``SoftmaxOutput``'s. The injected gradient is ``(p - onehot) *
    w_i`` with ``w_i = [noisy_i == mask_id] * block_length / masks in i's
    block``: the block's ``1 / t`` at the masked share ``t`` it was drawn
    at; an unmasked row has weight 0, injects nothing and counts for
    nothing. ``loss_value`` (the health stream's) is the unweighted
    cross-entropy of every row.

    Auxiliary state ``mask_count`` (4,): rows, masked rows, blocks and the
    sum of the weights seen by the training steps, modulo ``LOAD_WRAP``
    (whole numbers a float32 holds exactly, as ``MixtureOfExperts``'
    ``expert_load``); ``epoch_record`` makes ``fit``'s
    ``fit.epoch.diffusion_mask`` line of them."""

    LOAD_WRAP = 1 << 23

    params = {
        "mask_id": (Range(int, lo=0), REQUIRED, "the id of a masked position"),
        "block_length": (Range(int, lo=1), REQUIRED, "positions a block"),
        "grad_scale": (float, 1.0,
                       "multiplier applied to the injected gradient"),
    }
    is_loss = True

    def list_arguments(self):
        return ["data", "label", "noisy"]

    def list_auxiliary_states(self):
        return ["mask_count"]

    def infer_shape(self, in_shapes):
        d = self._known(in_shapes, 0)
        ids = next((tuple(s) for s in in_shapes[1:] if s is not None), None)
        if ids is None:
            raise MXNetError("MaskedDiffusionOutput: neither the label's "
                             "nor the noisy ids' shape is known")
        size = 1
        for n in ids:
            size *= n
        if size != d[0] or ids[-1] % self.block_length:
            raise MXNetError(
                f"MaskedDiffusionOutput: ids {ids} against {d[0]} rows of "
                f"logits and blocks of {self.block_length}")
        return [d, ids, ids], [d], [(4,)]

    def infer_dtype(self, in_dtypes):
        # integer ids beside float logits, as Embedding
        import numpy as np

        data = np.dtype(in_dtypes[0]) if in_dtypes[0] is not None \
            else np.dtype("float32")
        ids = [np.dtype(t) if t is not None else np.dtype("int32")
               for t in in_dtypes[1:]]
        return [data, *ids], [data], [np.dtype("float32")]

    def weights(self, noisy):
        """``(w, blocks with a mask)``: a weight a row (float32, the rows
        as the logits') and the count the weights add up to a block of."""
        masked = (noisy.astype(jnp.int32) == self.mask_id).reshape(
            -1, self.block_length)
        count = jnp.sum(masked, axis=1, keepdims=True)
        w = jnp.where(masked, self.block_length
                      / jnp.maximum(count, 1).astype(jnp.float32), 0.0)
        return w.reshape(-1), jnp.sum(count > 0)

    def fwd(self, ins, aux, is_train, rng):
        data, label, noisy = ins
        w, nonempty = self.weights(jax.lax.stop_gradient(noisy))
        # a weight a row is a validity mask that is not 0 or 1: a row of
        # weight 0 injects no gradient and counts for nothing
        out = _softmax_output_masked(data, label.reshape(-1), w,
                                     self.grad_scale, False)
        count = aux[0]
        if is_train:
            seen = jnp.stack([jnp.asarray(n, count.dtype) for n in (
                w.shape[0], jnp.sum(w > 0), w.shape[0] // self.block_length,
                self.block_length * nonempty)])
            count = (count + seen) % self.LOAD_WRAP
        return [out], [count]

    def loss_value(self, out, label, mask=None):
        """The health stream's scalar: the cross-entropy of EVERY row at
        weight 1. Its caller has the label alone, not the noisy ids, so
        this is not the weighted sum whose gradient the head injects
        (masked rows only, at a block's weight): a spike shows in both."""
        del mask
        return jnp.sum(_cross_entropy(out, label.reshape(-1))) \
            * self.grad_scale

    def epoch_record(self, before, after):
        """This epoch's rows (``OpProp.epoch_record``): ``rows`` the head
        saw, ``masked`` of them, the ``blocks`` they lie in and
        ``weight_sum``, ``block_length`` a block that had a mask."""
        rows, masked, blocks, weight_sum = (
            (after[0].astype("int64") - before[0].astype("int64"))
            % self.LOAD_WRAP).tolist()
        return "fit.epoch.diffusion_mask", {
            "rows": rows, "masked": masked, "blocks": blocks,
            "weight_sum": weight_sum, "block_length": self.block_length}


def _regression_vjp(transform, grad_fn):
    @jax.custom_vjp
    def op(data, label):
        return transform(data)

    def fwd(data, label):
        out = transform(data)
        return out, (out, label)

    def bwd(res, g):
        del g
        out, label = res
        d = grad_fn(out.astype(jnp.float32), label.astype(jnp.float32).reshape(out.shape))
        return d.astype(out.dtype), jnp.zeros_like(label)

    op.defvjp(fwd, bwd)
    return op


def _regression_vjp_masked(transform, grad_fn):
    """Masked twin of _regression_vjp: padded rows (mask 0) inject no
    gradient (PadPolicy tail-batch contract, see ops/registry.fwd_masked)."""

    @jax.custom_vjp
    def op(data, label, mask):
        return transform(data)

    def fwd(data, label, mask):
        out = transform(data)
        return out, (out, label, mask)

    def bwd(res, g):
        del g
        out, label, mask = res
        d = grad_fn(out.astype(jnp.float32),
                    label.astype(jnp.float32).reshape(out.shape))
        d = d * _row_mask(mask, d.ndim)
        return d.astype(out.dtype), jnp.zeros_like(label), jnp.zeros_like(mask)

    op.defvjp(fwd, bwd)
    return op


_linear_regression = _regression_vjp(lambda x: x, lambda o, l: o - l)
_logistic_regression = _regression_vjp(jax.nn.sigmoid, lambda o, l: o - l)
_mae_regression = _regression_vjp(lambda x: x, lambda o, l: jnp.sign(o - l))
_linear_regression_masked = _regression_vjp_masked(
    lambda x: x, lambda o, l: o - l)
_logistic_regression_masked = _regression_vjp_masked(
    jax.nn.sigmoid, lambda o, l: o - l)
_mae_regression_masked = _regression_vjp_masked(
    lambda x: x, lambda o, l: jnp.sign(o - l))


class _RegressionBase(OpProp):
    params = {"grad_scale": (float, 1.0, "gradient multiplier")}
    is_loss = True
    supports_loss_mask = True
    _kernel = None
    _kernel_masked = None
    _loss_elem = None  # elementwise loss whose grad is the injected one

    def loss_value(self, out, label, mask=None):
        o = out.astype(jnp.float32)
        l = label.astype(jnp.float32).reshape(out.shape)
        e = type(self)._loss_elem(o, l)
        if mask is not None:
            e = e * _row_mask(mask, e.ndim)
        return jnp.sum(e) * self.grad_scale

    def list_arguments(self):
        return ["data", "label"]

    def infer_shape(self, in_shapes):
        d = self._known(in_shapes, 0)
        return [d, d], [d], []

    def fwd(self, ins, aux, is_train, rng):
        out = type(self)._kernel(ins[0], ins[1])
        if self.grad_scale != 1.0:
            # fold the scale into the custom vjp via linearity of the grad
            out = _ScaleGrad(self.grad_scale)(out)
        return [out], []

    def fwd_masked(self, ins, aux, is_train, rng, mask):
        out = type(self)._kernel_masked(ins[0], ins[1], mask)
        if self.grad_scale != 1.0:
            out = _ScaleGrad(self.grad_scale)(out)
        return [out], []


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scale_grad(scale, x):
    return x


_scale_grad.defvjp(
    lambda scale, x: (x, None),
    lambda scale, res, g: (g * scale,),
)


class _ScaleGrad:
    def __init__(self, scale):
        self.scale = scale

    def __call__(self, x):
        return _scale_grad(self.scale, x)


@register_op("LinearRegressionOutput")
class LinearRegressionOutputOp(_RegressionBase):
    """Identity forward, (pred - label) gradient (reference:
    regression_output.cc:31)."""

    _kernel = staticmethod(_linear_regression)
    _kernel_masked = staticmethod(_linear_regression_masked)
    _loss_elem = staticmethod(lambda o, l: 0.5 * jnp.square(o - l))


@register_op("LogisticRegressionOutput")
class LogisticRegressionOutputOp(_RegressionBase):
    """Sigmoid forward, (pred - label) gradient (reference:
    regression_output.cc:36)."""

    _kernel = staticmethod(_logistic_regression)
    _kernel_masked = staticmethod(_logistic_regression_masked)
    # out is already sigmoid(data); grad (o - l) is BCE's
    _loss_elem = staticmethod(
        lambda o, l: -(l * jnp.log(o + 1e-12)
                       + (1.0 - l) * jnp.log(1.0 - o + 1e-12)))


@register_op("MAERegressionOutput")
class MAERegressionOutputOp(_RegressionBase):
    """Identity forward, sign(pred - label) gradient (L1 regression head;
    capability extension in the same family)."""

    _kernel = staticmethod(_mae_regression)
    _kernel_masked = staticmethod(_mae_regression_masked)
    _loss_elem = staticmethod(lambda o, l: jnp.abs(o - l))
