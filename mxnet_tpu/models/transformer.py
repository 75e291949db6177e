"""Transformer language model — the multi-chip flagship.

This is the model that exercises the full TPU-native parallel stack
(capabilities the reference lacks, SURVEY.md §5): a decoder-only LM whose
training step shards over a (dp, tp, sp) mesh —

  dp: batch sharding, gradient psum inserted by the SPMD partitioner
  tp: Megatron-style column/row parallel matmuls (parallel.tensor_parallel)
  sp: ring attention over the sequence axis (parallel.sequence)

Pure-functional: params are a flat dict (names match
``parallel.transformer_param_specs``), forward/loss are jit-traceable, and
``make_train_step`` returns a donated, sharded, fused step.
"""

from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError
from ..parallel.sequence import attention_reference, ring_self_attention
from ..parallel.tensor_parallel import transformer_param_specs

__all__ = ["transformer_lm_config", "TransformerLM"]


def transformer_lm_config(vocab_size=32000, d_model=512, n_heads=8, n_layers=4,
                          d_ff=None, max_len=2048, dtype=jnp.bfloat16,
                          attn_impl="auto", remat=False):
    """attn_impl: 'flash' (Pallas kernel), 'dense', or 'auto' (flash on TPU).

    ``remat``: run each decoder layer under ``jax.checkpoint`` — backward
    recomputes the layer instead of saving its interior activations, so
    saved-activation memory drops from O(n_layers * seq * d_ff) to
    O(n_layers * seq * d_model): the standard long-context lever (with
    ring attention over sp it is what lets sequence length scale to the
    HBM limit of the boundary activations alone)."""
    return {
        "vocab_size": vocab_size,
        "d_model": d_model,
        "n_heads": n_heads,
        "n_layers": n_layers,
        "d_ff": d_ff or 4 * d_model,
        "max_len": max_len,
        "dtype": dtype,
        "attn_impl": attn_impl,
        "remat": remat,
    }


def _layernorm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


class TransformerLM:
    def __init__(self, config):
        self.cfg = dict(config)
        self._dense_notice_given = False

    def _use_flash(self) -> bool:
        impl = self.cfg.get("attn_impl", "auto")
        if impl == "flash":
            return True
        if impl == "dense":
            return False
        return jax.default_backend() == "tpu"

    def _flash_fits_mesh(self, mesh, batch) -> bool:
        """The flash kernel runs per (dp, tp) shard under shard_map, which
        needs the batch and the heads to split evenly. An explicit
        ``attn_impl="flash"`` that cannot raises; ``auto`` takes the dense
        path instead and says so once per model."""
        if mesh is None:
            return True
        dp, tp = mesh.shape.get("dp", 1), mesh.shape.get("tp", 1)
        heads = self.cfg["n_heads"]
        if batch % dp == 0 and heads % tp == 0:
            return True
        why = (f"batch {batch} / heads {heads} do not split evenly over "
               f"the mesh (dp={dp}, tp={tp})")
        if self.cfg.get("attn_impl", "auto") == "flash":
            raise MXNetError(f"attn_impl='flash': {why}; pad the batch, "
                             "change the mesh, or ask for 'dense'")
        if not self._dense_notice_given:
            self._dense_notice_given = True
            logging.warning("TransformerLM attn_impl='auto': %s; using "
                            "dense attention", why)
        return False

    # -- parameters -----------------------------------------------------------
    def init_params(self, key) -> dict:
        cfg = self.cfg
        d, ff, v = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
        n = cfg["n_layers"]
        keys = jax.random.split(key, 4 + 4 * n)
        ki = iter(keys)

        def dense(key, shape, scale=None):
            scale = scale or 1.0 / math.sqrt(shape[0])
            return (jax.random.normal(key, shape, jnp.float32) * scale)

        params = {
            "embed": dense(next(ki), (v, d), scale=0.02),
            "pos_embed": dense(next(ki), (cfg["max_len"], d), scale=0.02),
            "final_norm_scale": jnp.ones((d,), jnp.float32),
            "final_norm_bias": jnp.zeros((d,), jnp.float32),
            "lm_head": dense(next(ki), (d, v)),
        }
        for i in range(n):
            params.update({
                f"layer{i}_wqkv": dense(next(ki), (d, 3 * d)),
                f"layer{i}_wo": dense(next(ki), (d, d)),
                f"layer{i}_w1": dense(next(ki), (d, ff)),
                f"layer{i}_b1": jnp.zeros((ff,), jnp.float32),
                f"layer{i}_w2": dense(next(ki), (ff, d)),
                f"layer{i}_b2": jnp.zeros((d,), jnp.float32),
                f"layer{i}_ln1_scale": jnp.ones((d,), jnp.float32),
                f"layer{i}_ln1_bias": jnp.zeros((d,), jnp.float32),
                f"layer{i}_ln2_scale": jnp.ones((d,), jnp.float32),
                f"layer{i}_ln2_bias": jnp.zeros((d,), jnp.float32),
            })
        return params

    def param_shardings(self, mesh: Mesh) -> dict:
        specs = transformer_param_specs(self.cfg["n_layers"])
        return {k: NamedSharding(mesh, specs.get(k, P())) for k in self.init_shapes()}

    def init_shapes(self):
        cfg = self.cfg
        d, ff, v = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
        shapes = {"embed": (v, d), "pos_embed": (cfg["max_len"], d),
                  "final_norm_scale": (d,), "final_norm_bias": (d,),
                  "lm_head": (d, v)}
        for i in range(cfg["n_layers"]):
            shapes.update({
                f"layer{i}_wqkv": (d, 3 * d), f"layer{i}_wo": (d, d),
                f"layer{i}_w1": (d, ff), f"layer{i}_b1": (ff,),
                f"layer{i}_w2": (ff, d), f"layer{i}_b2": (d,),
                f"layer{i}_ln1_scale": (d,), f"layer{i}_ln1_bias": (d,),
                f"layer{i}_ln2_scale": (d,), f"layer{i}_ln2_bias": (d,),
            })
        return shapes

    # -- forward --------------------------------------------------------------
    def forward(self, params, tokens, mesh: Mesh | None = None):
        """tokens [batch, seq] int32 -> logits [batch, seq, vocab] f32.

        With a mesh, activations carry (dp, sp, tp) sharding constraints and
        attention runs as ring attention when the sp axis is >1."""
        cfg = self.cfg
        dtype = cfg["dtype"]
        d, h = cfg["d_model"], cfg["n_heads"]
        hd = d // h
        use_sp = mesh is not None and mesh.shape.get("sp", 1) > 1

        def cst(x, spec):
            if mesh is None:
                return x
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))  # mxlint: disable=MX805 - the model's declared activation shardings; audited via its own comm plan

        seq = tokens.shape[1]
        x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
        x = x * jnp.asarray(math.sqrt(d), dtype)
        x = x + params["pos_embed"][:seq].astype(dtype)
        x = cst(x, P("dp", "sp", None))

        def layer_fn(x, lp):
            # attention block
            y = _layernorm(x, lp["ln1_scale"], lp["ln1_bias"])
            qkv = jnp.einsum("bsd,df->bsf", y, lp["wqkv"].astype(dtype),
                             preferred_element_type=jnp.float32).astype(dtype)
            qkv = qkv.reshape(qkv.shape[0], seq, 3, h, hd)
            q, k, v = (qkv[:, :, j].transpose(0, 2, 1, 3) for j in range(3))
            q = cst(q, P("dp", "tp", "sp", None))
            k = cst(k, P("dp", "tp", "sp", None))
            v = cst(v, P("dp", "tp", "sp", None))
            if use_sp:
                # flash blocks inside the ring on TPU; dense blocks in tests
                attn = ring_self_attention(mesh, q, k, v, causal=True,
                                           use_flash=self._use_flash())
            elif self._use_flash() and \
                    self._flash_fits_mesh(mesh, q.shape[0]):
                from ..ops.pallas import flash_attention
                if mesh is None:
                    attn = flash_attention(q, k, v, causal=True)
                else:
                    # pallas_call has no GSPMD partitioning rule; run the
                    # kernel per-shard over (dp, tp) via shard_map so the
                    # sharded train step keeps its partitioning.
                    from ..compat import shard_map
                    spec = P("dp", "tp", None, None)
                    attn = shard_map(
                        functools.partial(flash_attention, causal=True),
                        mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec, check_vma=False,
                    )(q, k, v)
            else:
                attn = attention_reference(q, k, v, causal=True)
            attn = attn.transpose(0, 2, 1, 3).reshape(x.shape[0], seq, d)
            attn = jnp.einsum("bsd,df->bsf", attn, lp["wo"].astype(dtype),
                              preferred_element_type=jnp.float32).astype(dtype)
            x = cst(x + attn, P("dp", "sp", None))

            # mlp block (column-parallel w1, row-parallel w2)
            y = _layernorm(x, lp["ln2_scale"], lp["ln2_bias"])
            u = jnp.einsum("bsd,df->bsf", y, lp["w1"].astype(dtype),
                           preferred_element_type=jnp.float32).astype(dtype)
            u = u + lp["b1"].astype(dtype)
            u = cst(u, P("dp", "sp", "tp"))
            u = jax.nn.gelu(u)
            z = jnp.einsum("bsf,fd->bsd", u, lp["w2"].astype(dtype),
                           preferred_element_type=jnp.float32).astype(dtype)
            z = z + lp["b2"].astype(dtype)
            return cst(x + z, P("dp", "sp", None))

        if cfg.get("remat"):
            # per-layer activation recompute: only the layer-boundary x is
            # saved for backward (see transformer_lm_config docstring)
            layer_fn = jax.checkpoint(layer_fn)
        layer_param_names = ("ln1_scale", "ln1_bias", "wqkv", "wo",
                             "ln2_scale", "ln2_bias", "w1", "b1", "w2", "b2")
        for i in range(cfg["n_layers"]):
            x = layer_fn(x, {n: params[f"layer{i}_{n}"]
                             for n in layer_param_names})

        x = _layernorm(x, params["final_norm_scale"], params["final_norm_bias"])
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(dtype),
                            preferred_element_type=jnp.float32)
        return cst(logits.astype(jnp.float32), P("dp", "sp", None))

    def loss(self, params, tokens, targets, mesh=None):
        logits = self.forward(params, tokens, mesh=mesh)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None].astype(jnp.int32),
                                   axis=-1)[..., 0]
        return jnp.mean(nll)

    # -- fused, sharded train step --------------------------------------------
    def _state_shardings(self, mesh, opt_state):
        """Optimizer-state sharding tree: a leaf shaped like its parameter
        inherits the parameter's sharding (Adam m/v); anything else (step
        counters) replicates."""
        pshard = self.param_shardings(mesh)
        repl = NamedSharding(mesh, P())
        return {
            k: jax.tree_util.tree_map(
                lambda leaf: pshard[k]
                if getattr(leaf, "ndim", 0) > 0 else repl, opt_state[k])
            for k in opt_state
        }

    def make_train_step(self, mesh: Mesh | None, lr=None, optimizer=None):
        """Donated, sharded train step. ``optimizer=None`` keeps the
        built-in SGD-momentum(0.9); any ``mxnet_tpu.optimizer.Optimizer``
        (e.g. ``opt.create('adamw', ...)``) runs fused in the step via its
        pure pytree path — pass the matching state from
        ``init_sharded(..., optimizer=opt)``.

        ``lr=None`` takes the optimizer's own lr (or 1e-3 for the
        built-in). lr_schedulers are rejected: the fused step carries no
        step counter — rebuild the step per phase (each build is a cache
        hit for unchanged lr) or train via FeedForward for scheduling."""
        if optimizer is not None and optimizer.lr_scheduler is not None:
            raise MXNetError(
                "make_train_step: lr_scheduler is not consulted by the "
                "fused step (no step counter); pass explicit lr per phase "
                "or use FeedForward")
        if lr is None:
            lr = optimizer.lr if optimizer is not None else 1e-3

        def step(params, moms, tokens, targets):
            loss, grads = jax.value_and_grad(
                lambda p: self.loss(p, tokens, targets, mesh=mesh)
            )(params)
            if optimizer is None:
                new_moms = {k: 0.9 * moms[k] + grads[k] for k in params}
                new_params = {k: params[k] - lr * new_moms[k]
                              for k in params}
            else:
                new_params, new_moms = optimizer.apply(params, grads, moms,
                                                       lr)
            return new_params, new_moms, loss

        if mesh is None:
            return jax.jit(step, donate_argnums=(0, 1))
        pshard = self.param_shardings(mesh)
        if optimizer is None:
            sshard = pshard
        else:
            # state sharding tree from a structural template (leaf SHAPES
            # don't matter here — only the tree structure and leaf ndim)
            template = optimizer.init_state_tree(
                {k: jnp.zeros((2,), jnp.float32) for k in pshard})
            sshard = self._state_shardings(mesh, template)
        dshard = NamedSharding(mesh, P("dp", "sp"))
        return jax.jit(
            step,
            in_shardings=(pshard, sshard, dshard, dshard),
            out_shardings=(pshard, sshard, NamedSharding(mesh, P())),
            donate_argnums=(0, 1),
        )

    def init_sharded(self, mesh: Mesh | None, seed=0, optimizer=None):
        """Initialize params (and optimizer state: momentum buffers for the
        built-in SGD, or ``optimizer``'s state tree) directly with their
        target shardings, so no single host materializes the full model."""
        params = self.init_params(jax.random.PRNGKey(seed))
        if mesh is None:
            if optimizer is None:
                return params, {k: jnp.zeros_like(v)
                                for k, v in params.items()}
            return params, optimizer.init_state_tree(params)
        sh = self.param_shardings(mesh)
        params = {k: jax.device_put(v, sh[k]) for k, v in params.items()}
        if optimizer is None:
            state = {k: jnp.zeros_like(v) for k, v in params.items()}
            return params, {k: jax.device_put(v, sh[k])
                            for k, v in state.items()}
        # structural template (tiny leaves) -> sharding tree, then create
        # the REAL state directly with its target shardings inside jit, so
        # no single device ever materializes the full unsharded state
        # (Adam m/v are 2x the model in f32)
        template = optimizer.init_state_tree(
            {k: jnp.zeros((2,), jnp.float32) for k in params})
        sshard = self._state_shardings(mesh, template)
        state = jax.jit(optimizer.init_state_tree,  # mxlint: disable=MX303
                        out_shardings=sshard)(params)  # one-shot init
        return params, state
