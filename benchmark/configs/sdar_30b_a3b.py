"""Plain float32 reference of SDAR-30B-A3B-Chat (JetLM, ``model_type:
sdar_moe``) under block-diffusion training, written from its public
config.json and the BD3-LM construction (Arriola et al., arXiv:2503.09573)
and independent of the program: straightforward ``jax.numpy``, dense
attention under a boolean mask built from ``b(i) = i // block_length`` and
the half a row lies in, blocked over queries (so that 2 x 4,096 positions
fit), a Python loop over experts, no kernel, no sort, nothing imported from
``mxnet_tpu``.

    logits(params, aux, ids)              ids (rows, 2 T): a sequence's noisy
                                          copy, then its clean copy; logits
                                          (rows x T, vocabulary rows) of the
                                          NOISY rows
    loss_and_grads(params, ids, labels)   the masked-diffusion loss (a masked
                                          row of a block at block_length /
                                          its masks) and its gradients (the
                                          tests)

Sizes come from the configuration's file beside this one
(``sdar_30b_a3b.json``) unless a ``config`` with the same keys is passed
(the tests' tiny sizes). ``params`` are the program's arrays under the
program's names (``layer3_q_weight``: (out, in), as a checkpoint has them).

The share. The reference is given the same share of the deployment as the
program and says so here: ``num_hidden_layers`` layers of the 48,
``num_experts`` experts of every layer from ``first_expert`` on (the
router's width, the published 128, is read off ``router_weight``; its top
``num_experts_per_tok`` are taken over ALL of them, and a pick on an expert
held elsewhere adds nothing), and the first ``vocab_size`` rows of the
vocabulary, the last of which is the mask token. What the absent experts
would have added is left out, as in the program.

Departures from the published description, each again a comment at its
line, marked DEPARTURE:
- per-head RMS norms on queries and keys (no key of config.json; the
  family SDAR is adapted from has them);
- block length 4 and the absorbing schedule at its discrete steps (a block
  of B with k masks weighs its masked rows B / k): ``not_given``;
- no shift between a row and its target: noisy row ``i`` predicts clean
  token ``i``;
- ``train_router`` false: a rank that trains alone keeps its router.

The controls that set ``reference_tolerance`` are the files beside this one
named ``sdar_30b_a3b_control_*.py``: each is this reference with one thing
wrong, and the runner is pointed at one by the ``reference`` key of a copy
of the configuration's file.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
QUERY_BLOCK = 256


def file_config():
    with open(os.path.join(HERE, "sdar_30b_a3b.json"), encoding="utf-8") as f:
        return json.load(f)


def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gamma


def rotate(x, positions, theta):
    """``x``: (batch, rows, heads, head_dim) at ``positions`` (rows,):
    the whole head rotates, dimension i paired with i + head_dim / 2 (the
    transformers library's rotate_half)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def seen(query_rows, t, block):
    """Boolean (len(query_rows), 2 t): which of the 2 t keys (noisy copy,
    then clean copy) each query row may attend."""
    key = jnp.arange(2 * t)[None, :]
    row = query_rows[:, None]
    q_clean, k_clean = row >= t, key >= t
    qb, kb = (row % t) // block, (key % t) // block
    return jnp.where(q_clean, k_clean & (kb <= qb),
                     (k_clean & (kb < qb)) | (~k_clean & (kb == qb)))


def attention(q, k, v, t, block):
    """Dense masked softmax attention, a block of queries at a time.
    q: (batch, 2 t, heads, d); k, v: (batch, 2 t, kv_heads, d)."""
    batch, rows, heads, d = q.shape
    group = heads // k.shape[2]
    k = jnp.repeat(k, group, axis=2)      # query head i reads kv head i // g
    v = jnp.repeat(v, group, axis=2)
    step = min(QUERY_BLOCK, rows)
    assert rows % step == 0

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, step, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / np.sqrt(d)
        allowed = seen(start + jnp.arange(step), t, block)
        weights = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)

    out = jax.lax.map(one_block, jnp.arange(0, rows, step))
    return jnp.moveaxis(out, 0, 1).reshape(batch, rows, heads, d)


def gated_ffn(x, gate_w, up_w, down_w):
    return (jax.nn.silu(x @ gate_w.T) * (x @ up_w.T)) @ down_w.T


def sparse_ffn(x, p, prefix, cfg):
    router = p[prefix + "router_weight"]
    scores = jax.nn.softmax(x @ router.T, axis=-1)    # all published experts
    rest, picks = scores, []
    for _ in range(cfg["num_experts_per_tok"]):       # the k largest, in turn
        e = jnp.argmax(rest, axis=-1)
        picks.append(e)
        rest = jnp.where(jax.nn.one_hot(e, router.shape[0], dtype=bool),
                         -jnp.inf, rest)
    picks = jnp.stack(picks, axis=-1)                 # (rows, k)
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    # norm_topk_prob: the picked probabilities divided by their sum
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    if not cfg.get("train_router", True):
        # DEPARTURE: a rank that holds a share and trains alone does not
        # update its router (the file's `assumed`)
        weights = jax.lax.stop_gradient(weights)
    first = cfg.get("first_expert", 0)
    out = jnp.zeros_like(x)                           # no shared expert
    # the share: only the experts held here; the others' part is left out
    for j in range(cfg["num_experts"]):
        w = jnp.sum(jnp.where(picks == first + j, weights, 0.0), axis=-1)
        out = out + w[:, None] * gated_ffn(
            x, p[prefix + "gate_weight"][j], p[prefix + "up_weight"][j],
            p[prefix + "down_weight"][j])
    return out


def forward(params, ids, cfg):
    """Logits (batch x T, vocabulary rows) of the noisy rows, float32."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    eps, d = cfg["rms_norm_eps"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    block, theta = cfg["block_length"], float(cfg["rope_theta"])
    batch, rows = ids.shape
    t = rows // 2
    # noisy row i and clean row i carry the same position i
    positions = jnp.arange(rows) % t
    x = p["embed_weight"][ids].reshape(batch * rows, -1)
    for l in range(cfg["num_hidden_layers"]):
        pre = f"layer{l}_"
        n = rms_norm(x, p[pre + "attn_norm_gamma"], eps)
        q = (n @ p[pre + "q_weight"].T).reshape(batch, rows, heads, d)
        k = (n @ p[pre + "k_weight"].T).reshape(batch, rows, kv_heads, d)
        v = (n @ p[pre + "v_weight"].T).reshape(batch, rows, kv_heads, d)
        # DEPARTURE (assumed): RMS norms a head on queries and keys, one
        # learnable vector of head_dim each, before the rotation
        q = rms_norm(q, p[pre + "q_norm_gamma"], eps)
        k = rms_norm(k, p[pre + "k_norm_gamma"], eps)
        a = attention(rotate(q, positions, theta),
                      rotate(k, positions, theta), v, t, block)
        h = x + a.reshape(batch * rows, heads * d) @ p[pre + "o_weight"].T
        x = h + sparse_ffn(rms_norm(h, p[pre + "ffn_norm_gamma"], eps), p,
                           pre + "moe_", cfg)
    # the head runs on the noisy rows; the clean rows' last output is unused
    noisy = x.reshape(batch, rows, -1)[:, :t].reshape(batch * t, -1)
    return rms_norm(noisy, p["final_norm_gamma"], eps) @ p["head_weight"].T


def logits(params, aux, ids, config=None):
    """The harness's check: ``aux`` (the counts) plays no part."""
    del aux
    return forward(params, jnp.asarray(ids, jnp.int32),
                   config or file_config())


def row_weights(noisy, cfg):
    """DEPARTURE (assumed schedule): a masked row of a block of B that has
    k masks weighs B / k (the block's 1 / t at t = k / B), any other 0."""
    block = cfg["block_length"]
    masked = (noisy == cfg["mask_id"]).reshape(-1, block)
    count = jnp.sum(masked, axis=1, keepdims=True)
    return jnp.where(masked, block / jnp.maximum(count, 1), 0.0).reshape(-1)


def loss_and_grads(params, ids, labels, config=None):
    """The masked-diffusion loss, summed over the masked noisy rows at
    their weights, and its gradient by every parameter (``fit`` divides by
    the batch's rows)."""
    cfg = config or file_config()
    ids = jnp.asarray(ids, jnp.int32)
    labels = jnp.asarray(labels, jnp.int32).reshape(-1)
    weights = row_weights(ids[:, :ids.shape[1] // 2], cfg)

    def loss(p):
        logp = jax.nn.log_softmax(forward(p, ids, cfg), axis=-1)
        return -jnp.sum(weights * jnp.take_along_axis(
            logp, labels[:, None], axis=1)[:, 0])

    return jax.value_and_grad(loss)(
        {k: jnp.asarray(v, jnp.float32) for k, v in params.items()})
