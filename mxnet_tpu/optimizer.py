"""Optimizers (reference: python/mxnet/optimizer.py — Optimizer registry,
SGD with momentum/weight-decay/grad-clip, ``get_updater``).

Two execution surfaces, same math:
  - the imperative ``update(index, weight, grad, state)`` path used by the
    KVStore updater contract (NDArray in/out, matches the reference exactly);
  - a pure ``apply(params, grads, states, lr) -> (params, states)`` pytree
    path the fused train step jits, so on TPU the whole update fuses into
    the backward program (no per-parameter dispatch).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import MXNetError, Registry
from .ndarray import NDArray, zeros

__all__ = ["Optimizer", "SGD", "Test", "Adam", "RMSProp", "AdaGrad", "create", "get_updater"]

OPTIMIZERS = Registry("optimizer")


class Optimizer:
    """Base optimizer. Subclasses implement create_state and pure _step."""

    def __init__(self, rescale_grad=1.0, lr=0.01, wd=0.0, clip_gradient=None,
                 lr_scheduler=None, arg_names=None, learning_rate=None):
        self.rescale_grad = rescale_grad
        # 'learning_rate' is the reference's kwarg name (optimizer.py SGD);
        # 'lr' is the short form used throughout this package — accept both.
        self.lr = lr if learning_rate is None else learning_rate
        self.wd = wd
        self.clip_gradient = clip_gradient
        self.lr_scheduler = lr_scheduler
        self.num_update = 0
        self._index_update_count = {}
        self.arg_names = arg_names

    @staticmethod
    def create_optimizer(name, **kwargs):
        return OPTIMIZERS.create(name, **kwargs)

    # -- imperative path (KVStore updater contract) ---------------------------
    def create_state(self, index: int, weight: NDArray):
        raise NotImplementedError

    def update(self, index: int, weight: NDArray, grad: NDArray, state):
        # one "update" = one optimization step, not one per parameter
        # (reference: _index_update_count in later MXNet; schedulers depend on it)
        self._index_update_count[index] = self._index_update_count.get(index, 0) + 1
        self.num_update = max(self._index_update_count.values())
        lr = self._get_lr()
        new_w, new_s = self._apply_one(weight._data, grad._data, state, lr)
        weight._set_data(new_w)
        return new_s

    def _get_lr(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def _apply_one(self, w, g, state, lr):
        raise NotImplementedError

    # -- pure pytree path (fused into the jitted train step) ------------------
    def init_state_tree(self, params: dict):
        return {k: self.tree_state(v) for k, v in params.items()}

    def init_comm_residual(self, params: dict, compression, num_devices):
        """Error-feedback residual for compressed gradient sync (comm/
        allreduce.py), or None when the mode needs no feedback.

        Lives on the optimizer because — like momentum — the residual is
        per-parameter training state accumulated in the optimizer's
        gradient units (pre-``rescale_grad`` sums): it must be (re)built
        whenever the optimizer or parameter set changes, and a checkpoint
        that restores one without the other restarts the error ledger."""
        from .comm import init_error_feedback

        return init_error_feedback(params, compression, num_devices)

    def tree_state(self, w):
        return None

    def apply(self, params: dict, grads: dict, states: dict, lr):
        """Pure functional update over parameter pytrees."""
        new_p, new_s = {}, {}
        for k, w in params.items():
            new_p[k], new_s[k] = self._apply_one(w, grads[k], states[k], lr)
        return new_p, new_s

    def _preprocess(self, w, g):
        g = g.astype(jnp.float32) * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        return g + self.wd * w.astype(jnp.float32)


@OPTIMIZERS.register("sgd")
class SGD(Optimizer):
    """SGD with momentum (reference: optimizer.py SGD)."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, weight.context)

    def tree_state(self, w):
        return None if self.momentum == 0.0 else jnp.zeros(w.shape, jnp.float32)

    def _apply_one(self, w, g, state, lr):
        g = self._preprocess(w, g)
        if self.momentum == 0.0:
            return (w.astype(jnp.float32) - lr * g).astype(w.dtype), state
        mom = state._data if isinstance(state, NDArray) else state
        mom = self.momentum * mom - lr * g
        new_w = (w.astype(jnp.float32) + mom).astype(w.dtype)
        if isinstance(state, NDArray):
            state._set_data(mom)
            return new_w, state
        return new_w, mom


@OPTIMIZERS.register("test")
class Test(Optimizer):
    """Test-only optimizer (reference: optimizer.py:162 Test) —
    w += rescale_grad * grad, state mirrors the weight."""

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context)

    def tree_state(self, w):
        return jnp.zeros(w.shape, jnp.float32)

    def _apply_one(self, w, g, state, lr):
        del lr
        new_w = (w.astype(jnp.float32)
                 + g.astype(jnp.float32) * self.rescale_grad).astype(w.dtype)
        if isinstance(state, NDArray):
            state._set_data(new_w.astype(jnp.float32))
            return new_w, state
        return new_w, new_w.astype(jnp.float32)


@OPTIMIZERS.register("adam")
class Adam(Optimizer):
    """Adam (capability extension; reference v0.5 ships only SGD).

    ``fused``: route the pure pytree path (``apply``) through the ONE
    blocked Pallas kernel (ops/pallas/adam.py) instead of the per-leaf
    elementwise tree — bitwise-identical results, same
    ``{name: (m, v, t)}`` state layout (checkpoints interchange freely);
    the step-time delta is not measured on a chip.
    None (default) reads the env gate ``MXNET_TPU_FUSED_ADAM``; the
    imperative KVStore path is unaffected.
    """

    def __init__(self, beta1=0.9, beta2=0.999, epsilon=1e-8, lr=0.001,
                 fused=None, **kwargs):
        super().__init__(lr=lr, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.fused = fused

    def _fused_active(self) -> bool:
        from .ops.pallas.adam import fused_resolve

        return fused_resolve(self.fused)

    def apply(self, params, grads, states, lr):
        if self._fused_active():
            from .ops.pallas.adam import fused_adam_apply

            return fused_adam_apply(self, params, grads, states, lr)
        return super().apply(params, grads, states, lr)

    def create_state(self, index, weight):
        # per-parameter step counter (a shared one would corrupt the bias
        # correction of every parameter after the first)
        return (zeros(weight.shape, weight.context),
                zeros(weight.shape, weight.context), [0])

    def tree_state(self, w):
        return (jnp.zeros(w.shape, jnp.float32), jnp.zeros(w.shape, jnp.float32),
                jnp.zeros((), jnp.float32))

    def _step_update(self, w32, mhat, vhat, lr):
        """The weight-update rule given bias-corrected moments (AdamW
        overrides to add its decoupled decay term)."""
        return w32 - lr * mhat / (jnp.sqrt(vhat) + self.epsilon)

    def _apply_one(self, w, g, state, lr):
        g = self._preprocess(w, g)
        m_state, v_state, t_state = state
        if isinstance(m_state, NDArray):  # imperative/KVStore path
            m, v = m_state._data, v_state._data
            t_state[0] += 1
            t = jnp.asarray(float(t_state[0]))
        else:  # pure pytree path (t is a traced scalar)
            m, v, t = m_state, v_state, t_state + 1.0
        m = self.beta1 * m + (1 - self.beta1) * g
        v = self.beta2 * v + (1 - self.beta2) * jnp.square(g)
        mhat = m / (1 - self.beta1**t)
        vhat = v / (1 - self.beta2**t)
        new_w = self._step_update(w.astype(jnp.float32), mhat, vhat,
                                  lr).astype(w.dtype)
        if isinstance(m_state, NDArray):
            m_state._set_data(m)
            v_state._set_data(v)
            return new_w, state
        return new_w, (m, v, t)


@OPTIMIZERS.register("adamw")
class AdamW(Adam):
    """Adam with DECOUPLED weight decay (capability extension; the
    transformer-training default). Unlike Adam's L2-through-the-gradient
    (``wd`` folded into g by _preprocess), the decay applies directly to
    the weight, scaled by lr — the AdamW formulation. Moments/bias
    correction are inherited; only the weight-update rule differs."""

    def __init__(self, weight_decay=0.01, decay_filter=None, **kwargs):
        if kwargs.get("wd"):
            raise MXNetError(
                "AdamW: use weight_decay (decoupled), not wd — passing wd "
                "would ALSO apply L2 through the gradient, double-"
                "regularizing")
        super().__init__(**kwargs)
        self.weight_decay = weight_decay
        # decay_filter(name) -> bool: False exempts a parameter (the
        # standard recipe exempts biases/LayerNorm/embeddings). None
        # decays everything. Name-aware masking rides the pytree path's
        # per-name loop (apply) and the imperative path's index->name
        # mapping (update, via arg_names) — both trace-time static.
        self.decay_filter = decay_filter

    def update(self, index, weight, grad, state):
        if self.decay_filter is None:
            return super().update(index, weight, grad, state)
        if not self.arg_names or not 0 <= index < len(self.arg_names):
            raise MXNetError(
                "AdamW.decay_filter needs parameter NAMES on the "
                "imperative path: set optimizer.arg_names (FeedForward and "
                "Module do this automatically) or drop the filter")
        wd = self.weight_decay
        try:
            if not self.decay_filter(self.arg_names[index]):
                self.weight_decay = 0.0
            return super().update(index, weight, grad, state)
        finally:
            self.weight_decay = wd

    def apply(self, params, grads, states, lr):
        if self._fused_active():
            # the fused kernel masks the decay per tile (decay_filter is
            # trace-time static), so it handles both filter cases
            from .ops.pallas.adam import fused_adam_apply

            return fused_adam_apply(self, params, grads, states, lr)
        if self.decay_filter is None:
            return super().apply(params, grads, states, lr)
        wd, new_p, new_s = self.weight_decay, {}, {}
        try:
            for k, w in params.items():
                self.weight_decay = wd if self.decay_filter(k) else 0.0
                new_p[k], new_s[k] = self._apply_one(w, grads[k],
                                                     states[k], lr)
        finally:
            self.weight_decay = wd
        return new_p, new_s

    def _step_update(self, w32, mhat, vhat, lr):
        return super()._step_update(w32, mhat, vhat, lr) \
            - lr * self.weight_decay * w32


@OPTIMIZERS.register("rmsprop")
class RMSProp(Optimizer):
    def __init__(self, gamma=0.9, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self.gamma, self.epsilon = gamma, epsilon

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context)

    def tree_state(self, w):
        return jnp.zeros(w.shape, jnp.float32)

    def _apply_one(self, w, g, state, lr):
        g = self._preprocess(w, g)
        acc = state._data if isinstance(state, NDArray) else state
        acc = self.gamma * acc + (1 - self.gamma) * jnp.square(g)
        new_w = (w.astype(jnp.float32) - lr * g / (jnp.sqrt(acc) + self.epsilon)).astype(w.dtype)
        if isinstance(state, NDArray):
            state._set_data(acc)
            return new_w, state
        return new_w, acc


@OPTIMIZERS.register("adagrad")
class AdaGrad(Optimizer):
    def __init__(self, epsilon=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context)

    def tree_state(self, w):
        return jnp.zeros(w.shape, jnp.float32)

    def _apply_one(self, w, g, state, lr):
        g = self._preprocess(w, g)
        acc = state._data if isinstance(state, NDArray) else state
        acc = acc + jnp.square(g)
        new_w = (w.astype(jnp.float32) - lr * g / (jnp.sqrt(acc) + self.epsilon)).astype(w.dtype)
        if isinstance(state, NDArray):
            state._set_data(acc)
            return new_w, state
        return new_w, acc


def create(name, **kwargs) -> Optimizer:
    """Create an optimizer by registered name (reference: opt.create)."""
    return OPTIMIZERS.create(name, **kwargs)


def get_updater(optimizer: Optimizer):
    """Closure with per-index state, the KVStore updater contract
    (reference: optimizer.py get_updater)."""
    states = {}

    def updater(index, grad, weight):
        if index not in states:
            states[index] = optimizer.create_state(index, weight)
        states[index] = optimizer.update(index, weight, grad, states[index]) or states[index]

    return updater
