"""Neural-network layer operators.

Reference counterparts under src/operator/: fully_connected-inl.h,
convolution-inl.h, deconvolution-inl.h, pooling-inl.h, batch_norm-inl.h,
dropout-inl.h, lrn-inl.h, activation-inl.h, leaky_relu-inl.h.

TPU-native design notes:
  - Convolution lowers to ``lax.conv_general_dilated``; the reference's
    im2col + grouped GEMM + workspace chunking (convolution-inl.h:68-140) is
    exactly what the compiler does better, so none of it is reimplemented.
  - Conv/Pooling take a ``layout`` param (NCHW default for reference parity;
    NHWC is the fast path on TPU — channels land on the lane dimension of the
    MXU/VPU so XLA needs no relayout transposes). Weights stay OIHW in both
    layouts so checkpoints map 1:1. BatchNorm takes ``axis`` for the channel
    dimension (1 for NCHW activations, -1 for NHWC).
  - Pooling is ``lax.reduce_window``; LRN is a windowed mean over channels.
  - BatchNorm carries aux state (moving_mean/moving_var, batch_norm-inl.h:88)
    functionally: fwd returns updated aux, the executor writes it back.
  - Dropout/RReLU consume an explicit PRNG key (replacing the engine-managed
    kRandom resource, include/mxnet/resource.h).
  - Compute dtype follows the input dtype; params may be float32 while
    activations are bfloat16 (mixed precision is handled at the model layer).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import OpProp, Range, REQUIRED, TupleParam, register_op


def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


@register_op("FullyConnected")
class FullyConnectedOp(OpProp):
    """Affine layer: Y = X·Wᵀ + b (reference: fully_connected-inl.h:53-118).

    Weight layout (num_hidden, input_dim) matches the reference so checkpoints
    map 1:1. The matmul contracts in the input dtype and accumulates f32 on
    the MXU (preferred_element_type)."""

    params = {
        "num_hidden": (Range(int, lo=1), REQUIRED, "number of output units"),
        "no_bias": (bool, False, "omit the bias term"),
    }

    def list_arguments(self):
        return ["data", "weight"] if self.no_bias else ["data", "weight", "bias"]

    def infer_shape(self, in_shapes):
        d = self._known(in_shapes, 0)
        in_dim = 1
        for x in d[1:]:
            in_dim *= x
        shapes = [d, (self.num_hidden, in_dim)]
        if not self.no_bias:
            shapes.append((self.num_hidden,))
        return shapes, [(d[0], self.num_hidden)], []

    def fwd(self, ins, aux, is_train, rng):
        x = ins[0]
        x = x.reshape((x.shape[0], -1))
        if not is_train:
            # serving path: under int8_predict_scope (Predictor
            # quantize="int8" / env MXNET_TPU_INT8_PREDICT) the matmul
            # runs the int8 Pallas kernel — per-channel weight scales,
            # f32 accumulate. Trace-time gate: armed when the program
            # first traces (ops/pallas/matmul.py).
            from .pallas.matmul import int8_matmul, int8_predict_active

            if int8_predict_active():
                y = int8_matmul(x.astype(jnp.float32),
                                ins[1]).astype(x.dtype)
                if not self.no_bias:
                    y = y + ins[2].astype(x.dtype)
                return [y], []
        w = ins[1].astype(x.dtype)
        y = lax.dot_general(
            x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ).astype(x.dtype)
        if not self.no_bias:
            y = y + ins[2].astype(x.dtype)
        return [y], []


@register_op("Convolution")
class ConvolutionOp(OpProp):
    """2-D convolution (reference: convolution-inl.h). Weights are OIHW in
    both layouts; ``layout`` only changes the activation layout."""

    params = {
        "kernel": (TupleParam(2), REQUIRED, "kernel (h, w)"),
        "stride": (TupleParam(2), (1, 1), "stride (h, w)"),
        "pad": (TupleParam(2), (0, 0), "zero-padding (h, w)"),
        "dilate": (TupleParam(2), (1, 1), "dilation (h, w) (extension)"),
        "num_filter": (Range(int, lo=1), REQUIRED, "number of output channels"),
        "num_group": (Range(int, lo=1), 1, "grouped-convolution group count"),
        "no_bias": (bool, False, "omit the bias term"),
        "workspace": (int, 512, "accepted for parity; XLA manages scratch"),
        "layout": (("NCHW", "NHWC"), "NCHW", "activation layout (NHWC = TPU fast path)"),
    }

    def list_arguments(self):
        return ["data", "weight"] if self.no_bias else ["data", "weight", "bias"]

    def _out_hw(self, h, w):
        kh, kw = self.kernel
        sh, sw = self.stride
        ph, pw = self.pad
        dh, dw = self.dilate
        eh, ew = dh * (kh - 1) + 1, dw * (kw - 1) + 1
        return (h + 2 * ph - eh) // sh + 1, (w + 2 * pw - ew) // sw + 1

    def infer_shape(self, in_shapes):
        d = self._known(in_shapes, 0)
        if len(d) != 4:
            raise MXNetError(f"Convolution expects 4-D input, got {d}")
        if self.layout == "NHWC":
            n, h, w, c = d
        else:
            n, c, h, w = d
        if c % self.num_group or self.num_filter % self.num_group:
            raise MXNetError("Convolution: channels not divisible by num_group")
        wshape = (self.num_filter, c // self.num_group) + self.kernel
        oh, ow = self._out_hw(h, w)
        out = (n, oh, ow, self.num_filter) if self.layout == "NHWC" else \
            (n, self.num_filter, oh, ow)
        shapes = [d, wshape] + ([] if self.no_bias else [(self.num_filter,)])
        return shapes, [out], []

    def fwd(self, ins, aux, is_train, rng):
        x = ins[0]
        w = ins[1].astype(x.dtype)
        if (self.kernel == (1, 1) and self.pad == (0, 0)
                and self.dilate == (1, 1) and self.num_group == 1
                and self.layout == "NHWC"):
            # Pointwise convs (over half of ResNet-scale conv count) lower as
            # a plain channel matmul on the MXU. Routing them through
            # conv_general_dilated lets XLA pick degenerate conv algorithms —
            # observed: the stage-1 1x1x64x64 conv compiled to a 56x56-window
            # convolution with pad=55 (activation as the kernel), ~80 GFLOP
            # of multiply-by-zero per image, 6x the whole model's real work.
            # dot_general is unambiguous; stride is a slice before the GEMM.
            sh, sw = self.stride
            if (sh, sw) != (1, 1):
                x = x[:, ::sh, ::sw, :]
            y = lax.dot_general(x, w[:, :, 0, 0],
                                (((3,), (1,)), ((), ())))
        else:
            # no preferred_element_type: its transpose rule mixes dtypes under
            # bf16 autodiff; TPU convs accumulate f32 for bf16 inputs anyway
            y = lax.conv_general_dilated(
                x,
                w,
                window_strides=self.stride,
                padding=[(self.pad[0], self.pad[0]), (self.pad[1], self.pad[1])],
                rhs_dilation=self.dilate,
                dimension_numbers=(self.layout, "OIHW", self.layout),
                feature_group_count=self.num_group,
            )
        if not self.no_bias:
            bshape = (1, 1, 1, -1) if self.layout == "NHWC" else (1, -1, 1, 1)
            y = y + ins[2].astype(x.dtype).reshape(bshape)
        return [y], []


@register_op("Deconvolution")
class DeconvolutionOp(OpProp):
    """Transposed convolution (reference: deconvolution-inl.h), implemented as
    input-dilated convolution with a spatially-flipped kernel — the native XLA
    formulation of conv-transpose."""

    params = {
        "kernel": (TupleParam(2), REQUIRED, "kernel (h, w)"),
        "stride": (TupleParam(2), (1, 1), "stride (h, w)"),
        "pad": (TupleParam(2), (0, 0), "padding (h, w)"),
        "num_filter": (Range(int, lo=1), REQUIRED, "number of output channels"),
        "num_group": (Range(int, lo=1), 1, "group count"),
        "no_bias": (bool, True, "omit the bias term"),
        "workspace": (int, 512, "accepted for parity"),
        "layout": (("NCHW", "NHWC"), "NCHW", "activation layout (NHWC = TPU fast path)"),
    }

    def list_arguments(self):
        return ["data", "weight"] if self.no_bias else ["data", "weight", "bias"]

    def infer_shape(self, in_shapes):
        d = self._known(in_shapes, 0)
        if self.layout == "NHWC":
            n, h, w, c = d
        else:
            n, c, h, w = d
        kh, kw = self.kernel
        sh, sw = self.stride
        ph, pw = self.pad
        oh = sh * (h - 1) + kh - 2 * ph
        ow = sw * (w - 1) + kw - 2 * pw
        wshape = (c, self.num_filter // self.num_group) + self.kernel
        out = (n, oh, ow, self.num_filter) if self.layout == "NHWC" else \
            (n, self.num_filter, oh, ow)
        shapes = [d, wshape] + ([] if self.no_bias else [(self.num_filter,)])
        return shapes, [out], []

    def fwd(self, ins, aux, is_train, rng):
        x = ins[0]
        w = ins[1].astype(x.dtype)
        kh, kw = self.kernel
        ph, pw = self.pad
        g = self.num_group
        # weight (c, f/g, kh, kw) -> OIHW (f, c/g, kh, kw) per group, flipped
        # spatially; lhs_dilation realizes the stride.
        w = jnp.flip(w, axis=(-2, -1))
        c = w.shape[0]
        if g > 1:
            w = w.reshape(g, c // g, -1, kh, kw).transpose((0, 2, 1, 3, 4))
            w_t = w.reshape(-1, c // g, kh, kw)
        else:
            w_t = w.transpose((1, 0, 2, 3))
        y = lax.conv_general_dilated(
            x,
            w_t,
            window_strides=(1, 1),
            padding=[(kh - 1 - ph, kh - 1 - ph), (kw - 1 - pw, kw - 1 - pw)],
            lhs_dilation=self.stride,
            dimension_numbers=(self.layout, "OIHW", self.layout),
            feature_group_count=self.num_group,
        )
        if not self.no_bias:
            bshape = (1, 1, 1, -1) if self.layout == "NHWC" else (1, -1, 1, 1)
            y = y + ins[2].astype(x.dtype).reshape(bshape)
        return [y], []


@register_op("Pooling")
class PoolingOp(OpProp):
    """Max/avg/sum pooling, NCHW or NHWC per ``layout`` (reference:
    pooling-inl.h).

    Matches the reference's ceil-mode output arithmetic
    ((x + 2p - k) / s + 1 rounded up when it doesn't divide; mshadow pool uses
    floor — v0.5 uses floor) — floor here, validated against numpy in tests."""

    params = {
        "kernel": (TupleParam(2), REQUIRED, "pooling window (h, w)"),
        "stride": (TupleParam(2), (1, 1), "stride (h, w)"),
        "pad": (TupleParam(2), (0, 0), "padding (h, w)"),
        "pool_type": (("max", "avg", "sum"), "max", "pooling reduction"),
        "global_pool": (bool, False, "pool over the full spatial extent"),
        "layout": (("NCHW", "NHWC"), "NCHW", "activation layout (NHWC = TPU fast path)"),
    }

    def _dims(self, h, w):
        if self.global_pool:
            return 1, 1
        kh, kw = self.kernel
        sh, sw = self.stride
        ph, pw = self.pad
        oh, ow = (h + 2 * ph - kh) // sh + 1, (w + 2 * pw - kw) // sw + 1
        if oh < 1 or ow < 1:
            from ..base import MXNetError
            raise MXNetError(
                f"Pooling: kernel {self.kernel} with pad {self.pad} exceeds "
                f"the input spatial extent ({h}, {w}); use global_pool=True "
                f"for whole-feature-map pooling")
        return oh, ow

    def _spatial(self):
        return (1, 2) if self.layout == "NHWC" else (2, 3)

    def infer_shape(self, in_shapes):
        d = self._known(in_shapes, 0)
        sh, sw = self._spatial()
        oh, ow = self._dims(d[sh], d[sw])
        out = list(d)
        out[sh], out[sw] = oh, ow
        return [d], [tuple(out)], []

    def fwd(self, ins, aux, is_train, rng):
        x = ins[0]
        sdims = self._spatial()
        if self.global_pool:
            # full-extent reduce: a plain reduction fuses better than a
            # degenerate reduce_window
            if self.pool_type == "max":
                y = jnp.max(x, axis=sdims, keepdims=True)  # native dtype: exact
            else:
                y = jnp.sum(x.astype(jnp.float32), axis=sdims, keepdims=True)
                if self.pool_type == "avg":
                    y = y / (x.shape[sdims[0]] * x.shape[sdims[1]])
            return [y.astype(x.dtype)], []
        kernel, stride, pad = self.kernel, self.stride, self.pad
        window = [1, 1, 1, 1]
        strides = [1, 1, 1, 1]
        padding = [(0, 0), (0, 0), (0, 0), (0, 0)]
        for i, d in enumerate(sdims):
            window[d] = kernel[i]
            strides[d] = stride[i]
            padding[d] = (pad[i], pad[i])
        if self.pool_type == "max":
            init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
            y = lax.reduce_window(x, init, lax.max, tuple(window), tuple(strides), tuple(padding))
        else:
            y = lax.reduce_window(x, 0.0, lax.add, tuple(window), tuple(strides), tuple(padding))
            if self.pool_type == "avg":
                y = y / (kernel[0] * kernel[1])
        return [y.astype(x.dtype)], []


@register_op("Activation")
class ActivationOp(OpProp):
    """Elementwise activations (reference: activation-inl.h + mshadow_op.h)."""

    params = {
        "act_type": (("relu", "sigmoid", "tanh", "softrelu", "silu"), REQUIRED,
                     "activation kind")
    }

    def fwd(self, ins, aux, is_train, rng):
        x = ins[0]
        if self.act_type == "relu":
            y = jax.nn.relu(x)
        elif self.act_type == "sigmoid":
            y = jax.nn.sigmoid(x)
        elif self.act_type == "tanh":
            y = jnp.tanh(x)
        elif self.act_type == "silu":  # x * sigmoid(x)
            y = jax.nn.silu(x)
        else:  # softrelu = log(1 + exp(x))
            y = jax.nn.softplus(x)
        return [y], []


@register_op("LeakyReLU")
class LeakyReLUOp(OpProp):
    """Leaky/parametric/randomized rectifiers (reference: leaky_relu-inl.h)."""

    params = {
        "act_type": (("leaky", "prelu", "rrelu", "elu"), "leaky", "variant"),
        "slope": (float, 0.25, "negative slope (leaky/elu)"),
        "lower_bound": (float, 0.125, "rrelu slope lower bound"),
        "upper_bound": (float, 0.334, "rrelu slope upper bound"),
    }

    need_rng = True

    def list_arguments(self):
        return ["data", "gamma"] if self.act_type == "prelu" else ["data"]

    def infer_shape(self, in_shapes):
        d = self._known(in_shapes, 0)
        if self.act_type == "prelu":
            return [d, (d[1],)], [d], []
        return [d], [d], []

    def fwd(self, ins, aux, is_train, rng):
        x = ins[0]
        if self.act_type == "leaky":
            return [jnp.where(x > 0, x, self.slope * x)], []
        if self.act_type == "elu":
            return [jnp.where(x > 0, x, self.slope * (jnp.exp(x) - 1.0))], []
        if self.act_type == "prelu":
            gamma = ins[1].astype(x.dtype).reshape((1, -1) + (1,) * (x.ndim - 2))
            return [jnp.where(x > 0, x, gamma * x)], []
        # rrelu: random slope per element in train, mean slope in eval
        if is_train:
            slope = jax.random.uniform(
                rng, x.shape, dtype=x.dtype, minval=self.lower_bound, maxval=self.upper_bound
            )
            slope = lax.stop_gradient(slope)
        else:
            slope = (self.lower_bound + self.upper_bound) / 2.0
        return [jnp.where(x > 0, x, slope * x)], []


@register_op("Dropout")
class DropoutOp(OpProp):
    """Inverted dropout (reference: dropout-inl.h — scales by 1/keep at train
    time, identity at eval)."""

    params = {"p": (Range(float, lo=0.0, hi=1.0, hi_exclusive=True), 0.5,
                    "fraction of units to drop")}
    need_rng = True

    def fwd(self, ins, aux, is_train, rng):
        x = ins[0]
        if not is_train or self.p <= 0.0:
            return [x], []
        keep = 1.0 - self.p
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return [jnp.where(mask, x / keep, 0.0).astype(x.dtype)], []


def _bn_reduce_axes(ndim, ch):
    return tuple(i for i in range(ndim) if i != ch)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _bn_act_train(x, g, b, eps, ch, relu):
    """Fused training-mode batch norm (optionally + ReLU) with a
    hand-written VJP.

    Why custom: under autodiff the naive formulation saves full-size f32
    intermediates (the upcast input, the centered product) as residuals —
    at ResNet-50 b256 that is ~10 GB of extra HBM traffic per step and
    pushes XLA into rematerialization. Here the residuals are exactly
    (x, g, b, mean, inv): the bf16 input (already live as the conv output)
    plus per-channel f32 vectors. Stats reduce in f32; the normalize and
    the dx elementwise run in the activation dtype with f32 per-channel
    scalars — the standard TPU fused-BN recipe.

    With ``relu`` (the executor's BatchNorm -> Activation(relu) fusion,
    executor.py), the ReLU mask is *recomputed* from the saved conv output
    in the backward (the pre-relu activation is per-channel affine in x,
    recomputable in-register), so the BN output is never materialized as a
    residual — one full-size HBM write + read saved per conv layer on a
    bandwidth-bound step.
    """
    return _bn_act_fwd(x, g, b, eps, ch, relu)[0]


def _bn_stats(x, eps, ch):
    # NOTE on the stats reductions: on the profiled v5e these VPU channel
    # reductions are the single largest step cost (~0.5 ms each). Ones-matmul
    # (MXU) and optimization_barrier reformulations were tried and measured
    # SLOWER or rewritten back to reduces by XLA (vector dots strength-reduce
    # to reduces; tall-skinny dots lower to degenerate convolutions); the
    # plain sibling-sum form below is the fastest found.
    axes = _bn_reduce_axes(x.ndim, ch)
    n = 1
    for a in axes:
        n *= x.shape[a]
    xf = x.astype(jnp.float32)
    # one-pass sibling reductions: a single read of x
    s1 = jnp.sum(xf, axis=axes)
    s2 = jnp.sum(jnp.square(xf), axis=axes)
    mean = s1 / n
    var = jnp.maximum(s2 / n - jnp.square(mean), 0.0)
    inv = lax.rsqrt(var + eps)
    return mean, var, inv, n


def _bn_affine(x, g, b, mean, inv, ch):
    """y = x·scale + shift with per-channel f32 scalars, applied in x.dtype.
    Shared by forward and backward so the mask recompute is bit-identical."""
    bshape = tuple(-1 if i == ch else 1 for i in range(x.ndim))
    scale = g * inv
    shift = b - mean * scale
    return x * scale.reshape(bshape).astype(x.dtype) + \
        shift.reshape(bshape).astype(x.dtype)


def _bn_act_fwd(x, g, b, eps, ch, relu):
    mean, var, inv, _ = _bn_stats(x, eps, ch)
    y = _bn_affine(x, g, b, mean, inv, ch)
    if relu:
        y = jnp.maximum(y, 0)
    return (y, mean, var), (x, g, b, mean, inv)


def _bn_core_bwd(x, g, mean, inv, dy, ch):
    """Shared BN backward math given the (already masked) cotangent."""
    axes = _bn_reduce_axes(x.ndim, ch)
    n = 1
    for a in axes:
        n *= x.shape[a]
    bshape = tuple(-1 if i == ch else 1 for i in range(x.ndim))
    mean_b = mean.reshape(bshape)
    inv_b = inv.reshape(bshape)
    xhat = (x - mean_b.astype(x.dtype)) * inv_b.astype(x.dtype)
    dyf = dy.astype(jnp.float32)
    xhat_f = (x.astype(jnp.float32) - mean_b) * inv_b
    dbeta = jnp.sum(dyf, axis=axes)
    dgamma = jnp.sum(dyf * xhat_f, axis=axes)
    # dx = g·inv · (dy - Σdy/n - x̂·Σ(dy·x̂)/n), elementwise in dy.dtype
    k = (g * inv).reshape(bshape).astype(dy.dtype)
    dx = k * (dy - (dbeta / n).reshape(bshape).astype(dy.dtype)
              - xhat * (dgamma / n).reshape(bshape).astype(dy.dtype))
    return dx.astype(x.dtype), dgamma, dbeta


def _bn_act_bwd(eps, ch, relu, res, cts):
    x, g, b, mean, inv = res
    dy = cts[0]  # mean/var outputs feed stop_gradient'd aux: cotangents zero
    if relu:
        # recompute the pre-relu activation with the forward's exact
        # expression and dtype, so the mask is bit-identical
        dy = jnp.where(_bn_affine(x, g, b, mean, inv, ch) > 0, dy,
                       jnp.zeros((), dy.dtype))
    return _bn_core_bwd(x, g, mean, inv, dy, ch)


_bn_act_train.defvjp(_bn_act_fwd, _bn_act_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _bn_add_relu_train(x, g, b, z, eps, ch):
    """Fused BatchNorm + residual-add + ReLU (training) — the executor's
    fusion pass routes the bottleneck tail BatchNorm -> (+shortcut) ->
    Activation(relu) here. Residuals: x (conv output, already live), z (the
    shortcut, already live as a neighbouring residual), and per-channel
    stats; the block output is never saved and the mask is recomputed —
    one block-sized HBM write + read removed per residual block."""
    return _bn_add_relu_fwd(x, g, b, z, eps, ch)[0]


def _bn_add_relu_fwd(x, g, b, z, eps, ch):
    mean, var, inv, _ = _bn_stats(x, eps, ch)
    y = jnp.maximum(_bn_affine(x, g, b, mean, inv, ch) + z, 0)
    return (y, mean, var), (x, g, b, z, mean, inv)


def _bn_add_relu_bwd(eps, ch, res, cts):
    x, g, b, z, mean, inv = res
    dy = cts[0]
    pre = _bn_affine(x, g, b, mean, inv, ch) + z  # exact fwd expression
    dy = jnp.where(pre > 0, dy, jnp.zeros((), dy.dtype))
    dx, dgamma, dbeta = _bn_core_bwd(x, g, mean, inv, dy, ch)
    return dx, dgamma, dbeta, dy.astype(z.dtype)


_bn_add_relu_train.defvjp(_bn_add_relu_fwd, _bn_add_relu_bwd)


@register_op("BatchNorm")
class BatchNormOp(OpProp):
    """Batch normalization with running-stat aux state (reference:
    batch_norm-inl.h; aux moving_mean/moving_var at :88-108,273).

    Train: normalize by batch stats, update running stats in f32.
    Eval: normalize by running stats. Gamma/beta are per-channel (axis 1 for
    NCHW, last axis for 2-D inputs — matching the reference's behavior on
    fully-connected activations)."""

    params = {
        "eps": (Range(float, lo=0.0), 1e-3, "numerical stability constant"),
        "momentum": (Range(float, lo=0.0, hi=1.0), 0.9, "running-average decay"),
        "fix_gamma": (bool, False, "freeze gamma at 1"),
        "axis": (int, 1, "channel axis (1 for NCHW, -1/3 for NHWC)"),
    }

    def list_arguments(self):
        return ["data", "gamma", "beta"]

    def list_auxiliary_states(self):
        return ["moving_mean", "moving_var"]

    def _channels(self, d):
        if len(d) < 2:
            return d[0]
        return d[self.axis % len(d)]

    def infer_shape(self, in_shapes):
        d = self._known(in_shapes, 0)
        c = (self._channels(d),)
        return [d, c, c], [d], [c, c]

    def fwd(self, ins, aux, is_train, rng):
        return self._fwd_impl(ins, aux, is_train, relu=False)

    def fwd_fused_relu(self, ins, aux, is_train, rng):
        """BatchNorm+ReLU in one op — target of the executor's fusion pass
        (executor.py) for BatchNorm -> Activation(relu) chains."""
        return self._fwd_impl(ins, aux, is_train, relu=True)

    def fwd_fused_add_relu(self, ins, aux, is_train, rng):
        """BatchNorm + residual add + ReLU — target of the executor's fusion
        pass for BatchNorm -> _Plus -> Activation(relu) (bottleneck tails).
        ``ins`` is [x, gamma, beta, z] with z the shortcut operand."""
        return self._fwd_impl(ins[:3], aux, is_train, relu=True, z=ins[3])

    def _fwd_impl(self, ins, aux, is_train, relu, z=None):
        x, gamma, beta = ins
        moving_mean, moving_var = aux
        ch = 1 if x.ndim == 2 else self.axis % x.ndim
        g = (jnp.ones_like(gamma) if self.fix_gamma else gamma).astype(jnp.float32)
        b = beta.astype(jnp.float32)
        if is_train:
            if z is not None:
                y, mean, var = _bn_add_relu_train(x, g, b, z, self.eps, ch)
            else:
                y, mean, var = _bn_act_train(x, g, b, self.eps, ch, relu)
            new_mean = self.momentum * moving_mean + (1 - self.momentum) * mean
            new_var = self.momentum * moving_var + (1 - self.momentum) * var
            return [y], [lax.stop_gradient(new_mean), lax.stop_gradient(new_var)]
        inv = lax.rsqrt(moving_var + self.eps)
        y = _bn_affine(x, g, b, moving_mean, inv, ch)
        if z is not None:
            y = y + z
        if relu:
            y = jnp.maximum(y, 0)
        return [y], [moving_mean, moving_var]


@register_op("LRN")
class LRNOp(OpProp):
    """Local response normalization across channels (reference: lrn-inl.h):
    y = x / (knorm + alpha/n * sum_{window} x²)^beta."""

    params = {
        "nsize": (Range(int, lo=1), REQUIRED, "normalization window (channels)"),
        "alpha": (float, 1e-4, "scale"),
        "beta": (float, 0.75, "exponent"),
        "knorm": (float, 2.0, "additive constant"),
    }

    def fwd(self, ins, aux, is_train, rng):
        x = ins[0]
        xf = x.astype(jnp.float32)
        half = self.nsize // 2
        sq = jnp.square(xf)
        # windowed channel sum via reduce_window on axis 1
        window = (1, self.nsize, 1, 1)
        pads = ((0, 0), (half, self.nsize - 1 - half), (0, 0), (0, 0))
        ssum = lax.reduce_window(sq, 0.0, lax.add, window, (1, 1, 1, 1), pads)
        y = xf * lax.pow(self.knorm + (self.alpha / self.nsize) * ssum, -self.beta)
        return [y.astype(x.dtype)], []
