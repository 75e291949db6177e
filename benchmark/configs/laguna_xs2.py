"""Plain float32 reference of Laguna-XS.2 (poolside, ``model_type:
laguna``), written from its public config.json and independent of the
program: straightforward ``jax.numpy``, dense masked attention blocked over
queries (so that 8,192 positions fit), a Python loop over experts, no
kernel, no sort, nothing imported from ``mxnet_tpu``.

    logits(params, aux, ids)              (rows x positions, vocabulary rows)
    loss_and_grads(params, ids, labels)   summed next-token cross-entropy
                                          and its gradients (the tests)

Sizes come from the configuration's file beside this one
(``laguna_xs2.json``) unless a ``config`` with the same keys is passed (the
tests' tiny sizes). ``params`` are the program's arrays under the
program's names (``layer3_q_weight``: (out, in), as a checkpoint has them).

The share. The reference is given the same share of the deployment as the
program and says so here: ``num_hidden_layers`` layers of the 40,
``num_experts`` experts of every sparse layer from ``first_expert`` on
(the router's width, the published 256, is read off ``router_weight``; its
top ``num_experts_per_tok`` are taken over ALL of them, and a pick on an
expert held elsewhere adds nothing), and the first ``vocab_size`` rows of
the vocabulary. What the absent experts would have added is left out, as
in the program; the shared expert is whole.

Departures from the source are comments at their lines, marked DEPARTURE.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
QUERY_BLOCK = 256


def file_config():
    with open(os.path.join(HERE, "laguna_xs2.json"), encoding="utf-8") as f:
        return json.load(f)


def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gamma


def inverse_frequencies(rope, head_dim):
    """``rotary_dim / 2`` inverse frequencies of one layer type's
    ``rope_parameters`` entry (float64 numpy)."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = float(rope["rope_theta"])
    i = np.arange(dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / dim)
    if rope.get("rope_type", "default") == "default":
        return plain, 1.0
    # YaRN: the dimension whose wavelength makes `turns` turns within the
    # original context
    original = rope["original_max_position_embeddings"]

    def dimension(turns):
        return dim * np.log(original / (turns * 2 * np.pi)) \
            / (2 * np.log(theta))

    lo = max(np.floor(dimension(rope["beta_fast"])), 0)
    hi = min(np.ceil(dimension(rope["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    r = 1.0 - np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return (1 - r) * plain / rope["factor"] + r * plain, \
        float(rope["attention_factor"])


def rotate(x, inv_freq, factor):
    """``x``: (batch, positions, heads, head_dim)."""
    half = len(inv_freq)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(angle) * factor)[None, :, None, :]
    sin = (jnp.sin(angle) * factor)[None, :, None, :]
    # DEPARTURE (assumed): dimension i pairs with i + rotary_dim / 2 (the
    # "rotate_half" convention of the transformers library); the config
    # does not say, and an interleaved pairing is the same model up to a
    # fixed permutation of each head's query and key columns
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def attention(q, k, v, window):
    """Dense masked softmax attention, a block of queries at a time.
    q: (batch, T, heads, d); k, v: (batch, T, kv_heads, d)."""
    batch, t, heads, d = q.shape
    group = heads // k.shape[2]
    k = jnp.repeat(k, group, axis=2)      # query head i reads kv head i // g
    v = jnp.repeat(v, group, axis=2)
    block = min(QUERY_BLOCK, t)
    assert t % block == 0
    key_pos = jnp.arange(t)[None, :]

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / np.sqrt(d)
        query_pos = start + jnp.arange(block)[:, None]
        seen = key_pos <= query_pos
        if window:
            seen &= query_pos - key_pos < window
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)

    out = jax.lax.map(one_block, jnp.arange(0, t, block))
    return jnp.moveaxis(out, 0, 1).reshape(batch, t, heads, d)


def gated_ffn(x, gate_w, up_w, down_w):
    return (jax.nn.silu(x @ gate_w.T) * (x @ up_w.T)) @ down_w.T


def sparse_ffn(x, p, prefix, cfg):
    router = p[prefix + "router_weight"]
    # DEPARTURE (assumed): sigmoid scores normalised over the picked
    # experts; the config names no score function. No routing bias and no
    # balancing loss: it has no key for either
    scores = jax.nn.sigmoid(x @ router.T)             # all published experts
    top_k = cfg["num_experts_per_tok"]
    rest, picks = scores, []
    for _ in range(top_k):                            # the k largest, in turn
        e = jnp.argmax(rest, axis=-1)
        picks.append(e)
        rest = jnp.where(jax.nn.one_hot(e, router.shape[0], dtype=bool),
                         -jnp.inf, rest)
    picks = jnp.stack(picks, axis=-1)                 # (rows, k)
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    weights = cfg["moe_routed_scaling_factor"] * picked \
        / jnp.sum(picked, axis=-1, keepdims=True)     # on the OUTPUT
    if not cfg.get("train_router", True):
        # DEPARTURE: a rank that holds a share and trains alone does not
        # update its router: its own experts' part of the router's
        # gradient, without the other ranks' parts, teaches the router to
        # route away from the experts held here (the file's `assumed`)
        weights = jax.lax.stop_gradient(weights)
    first = cfg.get("first_expert", 0)
    out = gated_ffn(x, p[prefix + "shared_gate_weight"],
                    p[prefix + "shared_up_weight"],
                    p[prefix + "shared_down_weight"])
    # the share: only the experts held here; the others' part is left out
    for j in range(cfg["num_experts"]):
        w = jnp.sum(jnp.where(picks == first + j, weights, 0.0), axis=-1)
        out = out + w[:, None] * gated_ffn(
            x, p[prefix + "gate_weight"][j], p[prefix + "up_weight"][j],
            p[prefix + "down_weight"][j])
    return out


def forward(params, ids, cfg):
    """Logits (batch x positions, vocabulary rows) in float32."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    eps, d = cfg["rms_norm_eps"], cfg["head_dim"]
    kv_heads = cfg["num_key_value_heads"]
    batch, t = ids.shape
    x = p["embed_weight"][ids].reshape(batch * t, -1)
    for l in range(cfg["num_hidden_layers"]):
        pre = f"layer{l}_"
        kind = cfg["layer_types"][l]
        heads = cfg["num_attention_heads_per_layer"][l]
        n = rms_norm(x, p[pre + "attn_norm_gamma"], eps)
        q = (n @ p[pre + "q_weight"].T).reshape(batch, t, heads, d)
        k = (n @ p[pre + "k_weight"].T).reshape(batch, t, kv_heads, d)
        v = (n @ p[pre + "v_weight"].T).reshape(batch, t, kv_heads, d)
        inv_freq, factor = inverse_frequencies(cfg["rope_parameters"][kind], d)
        # DEPARTURE (assumed): no query/key normalisation: no key for it
        a = attention(rotate(q, inv_freq, factor), rotate(k, inv_freq, factor),
                      v, cfg["sliding_window"]
                      if kind == "sliding_attention" else 0)
        if cfg["gating"]:
            # DEPARTURE (assumed): one gate a head and position, from the
            # layer's normalised input: an elementwise gate would add 0.63 B
            # parameters to a total that adds up to the published 33.4 B
            # without it
            a = a * jax.nn.sigmoid(n @ p[pre + "gate_weight"].T)[
                ..., None].reshape(batch, t, heads, 1)
        h = x + a.reshape(batch * t, heads * d) @ p[pre + "o_weight"].T
        n = rms_norm(h, p[pre + "ffn_norm_gamma"], eps)
        if cfg["mlp_layer_types"][l] == "sparse":
            f = sparse_ffn(n, p, pre + "moe_", cfg)
        else:
            f = gated_ffn(n, p[pre + "ffn_gate_weight"],
                          p[pre + "ffn_up_weight"], p[pre + "ffn_down_weight"])
        x = h + f
    return rms_norm(x, p["final_norm_gamma"], eps) @ p["head_weight"].T


def logits(params, aux, ids, config=None):
    """The harness's check: ``aux`` (the pick counts) plays no part."""
    del aux
    return forward(params, jnp.asarray(ids, jnp.int32),
                   config or file_config())


def loss_and_grads(params, ids, labels, config=None):
    """Summed cross-entropy of every position's next token, and its
    gradient by every parameter (``fit`` divides by the batch's rows)."""
    cfg = config or file_config()
    ids = jnp.asarray(ids, jnp.int32)
    labels = jnp.asarray(labels, jnp.int32).reshape(-1)

    def loss(p):
        logp = jax.nn.log_softmax(forward(p, ids, cfg), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1))

    return jax.value_and_grad(loss)(
        {k: jnp.asarray(v, jnp.float32) for k, v in params.items()})
