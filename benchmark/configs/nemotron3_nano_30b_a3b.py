"""Plain float32 reference of NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type:
nemotron_h``), written from its public config.json and the Mamba-2 paper
(Dao & Gu, arXiv:2405.21060), independent of the program: straightforward
``jax.numpy``, the selective scan as its PER-POSITION recurrence (a
``lax.scan`` over positions, where the program computes the chunked dual
form), the causal convolution written out, dense masked attention blocked
over queries (so that 8,192 positions fit), a Python loop over the held
experts, no kernel, no sort, nothing imported from ``mxnet_tpu``.

    logits(params, aux, ids)              (rows x positions, vocabulary rows)
    loss_and_grads(params, aux, ids, labels)
                                          summed next-token cross-entropy
                                          and its gradients (the tests)
    bias_step(params, aux, ids, rate)     the selection biases after a
                                          training step on ``ids`` (the tests)

Sizes come from the configuration's file beside this one
(``nemotron3_nano_30b_a3b.json``) unless a ``config`` with the same keys is
passed (the tests' tiny sizes). ``params`` are the program's arrays under
the program's names (``layer0_mamba_in_weight``: (out, in), as a
checkpoint has them); ``aux`` holds each expert layer's selection bias,
``layer<l>_moe_selection_bias`` (the router's width), as training left it.

The share. The reference is given the same share of the deployment as the
program and says so here: the first ``num_hidden_layers`` letters of
``hybrid_override_pattern``, ``n_routed_experts`` experts of every expert
layer from ``first_expert`` on (the router's width, the published 128, is
read off ``router_weight``; its top ``num_experts_per_tok`` are taken over
ALL of them), and the first ``vocab_size`` rows of the vocabulary. What
the absent experts would have added is left out, as in the program; the
shared expert is whole.

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
    with open(os.path.join(HERE, "nemotron3_nano_30b_a3b.json"),
              encoding="utf-8") as f:
        return json.load(f)


def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gamma


def silu(x):
    return x * jax.nn.sigmoid(x)


def softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def causal_conv(x, weight, bias):
    """x (batch, T, C), weight (C, K): position t reads t - K + 1 .. t, the
    last column of the weight on t itself; zeros before the sequence."""
    k = weight.shape[1]
    out = bias
    for shift in range(k):          # weight[:, k - 1 - shift] on t - shift
        moved = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :x.shape[1]]
        out = out + moved * weight[:, k - 1 - shift]
    return out


def selective_scan(x, dt, a, b, c):
    """The recurrence, one position at a time: x (batch, T, H, P), dt
    (batch, T, H), a (H,), b and c (batch, T, G, N); head h reads group h
    // (H / G). S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t,
    the state (batch, H, P, N) from zero."""
    heads, groups = x.shape[2], b.shape[2]
    b = jnp.repeat(b, heads // groups, axis=2)
    c = jnp.repeat(c, heads // groups, axis=2)

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[:, :, None, None] * state \
            + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    start = jnp.zeros(x.shape[:1] + (heads, x.shape[3], b.shape[3]),
                      jnp.float32)
    _, y = jax.lax.scan(step, start, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def mamba_mixer(n, p, pre, cfg, batch, t):
    heads, dim = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    inner = heads * dim
    proj = n @ p[pre + "in_weight"].T                 # [z | xBC | dt]
    z, xbc = proj[:, :inner], proj[:, inner:2 * inner + 2 * groups * state]
    dt = proj[:, 2 * inner + 2 * groups * state:]
    xbc = silu(causal_conv(xbc.reshape(batch, t, -1), p[pre + "conv_weight"],
                           p[pre + "conv_bias"]))
    x = xbc[..., :inner].reshape(batch, t, heads, dim)
    b = xbc[..., inner:inner + groups * state].reshape(batch, t, groups, state)
    c = xbc[..., inner + groups * state:].reshape(batch, t, groups, state)
    # time_step_limit is absent from the config: no clamp
    dt = softplus(dt.reshape(batch, t, heads) + p[pre + "dt_bias"])
    a = -jnp.exp(p[pre + "A_log"])
    y = selective_scan(x, dt, a, b, c) + p[pre + "D"][:, None] * x
    # DEPARTURE (assumed): the gated norm takes its mean square over each of
    # the n_groups groups of channels (mamba_ssm's RMSNormGated with
    # group_size = inner / n_groups, norm_before_gate False), where the
    # transformers Mamba2 torch path takes it over the whole width
    gated = (y.reshape(batch * t, inner) * silu(z)).reshape(
        batch * t, groups, inner // groups)
    normed = rms_norm(gated, 1.0, cfg["layer_norm_epsilon"]).reshape(
        batch * t, inner) * p[pre + "norm_gamma"]
    return normed @ p[pre + "out_weight"].T


def attention(q, k, v):
    """Dense causal softmax attention, a block of queries at a time. q
    (batch, T, heads, d); k, v (batch, T, heads, d)."""
    batch, t, heads, d = q.shape
    block = min(QUERY_BLOCK, t)
    assert t % block == 0
    key_pos = jnp.arange(t)[None, :]

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / np.sqrt(d)
        query_pos = start + jnp.arange(block)[:, None]
        weights = jax.nn.softmax(
            jnp.where(key_pos <= query_pos, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)

    out = jax.lax.map(one_block, jnp.arange(0, t, block))
    return jnp.moveaxis(out, 0, 1).reshape(batch, t, heads, d)


def grouped_query_attention(n, p, pre, cfg, batch, t):
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    q = (n @ p[pre + "q_weight"].T).reshape(batch, t, heads, d)
    k = (n @ p[pre + "k_weight"].T).reshape(batch, t, kv, d)
    v = (n @ p[pre + "v_weight"].T).reshape(batch, t, kv, d)
    # no rotary positions: Nemotron-H's attention layers take none (the
    # Mamba-2 layers carry position); rope_theta and partial_rotary_factor
    # are in the config and unused by the model's modeling code
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    return attention(q, k, v).reshape(batch * t, heads * d) \
        @ p[pre + "o_weight"].T


def relu2_ffn(x, up_w, down_w):
    return jnp.square(jnp.maximum(x @ up_w.T, 0.0)) @ down_w.T


def sparse_ffn(x, p, prefix, bias, cfg, loads=None):
    router = p[prefix + "router_weight"]
    scores = jax.nn.sigmoid(x @ router.T)             # all published experts
    # n_group = topk_group = 1: the picks are the k largest of the scores
    # plus the selection bias (e_score_correction_bias), over all experts
    choose = scores + bias[None, :]
    picks = []
    for _ in range(cfg["num_experts_per_tok"]):       # the k largest, in turn
        e = jnp.argmax(choose, axis=-1)
        picks.append(e)
        choose = jnp.where(jax.nn.one_hot(e, router.shape[0], dtype=bool),
                           -jnp.inf, choose)
    picks = jnp.stack(picks, axis=-1)                 # (rows, k)
    if loads is not None:
        loads.append(jnp.sum(jax.nn.one_hot(picks, router.shape[0]),
                             axis=(0, 1)))
    # ... and the weights the scores' alone: the bias selects, not weighs
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    weights = cfg["routed_scaling_factor"] * picked \
        / jnp.sum(picked, axis=-1, keepdims=True)     # norm_topk_prob
    if not cfg.get("train_router", True):
        # DEPARTURE: a rank that holds a share and trains alone does not
        # update its router by gradient (the file's `assumed`)
        weights = jax.lax.stop_gradient(weights)
    first = cfg.get("first_expert", 0)
    out = relu2_ffn(x, p[prefix + "shared_up_weight"],
                    p[prefix + "shared_down_weight"])
    # the share: only the experts held here; the others' part is left out
    for j in range(cfg["n_routed_experts"]):
        w = jnp.sum(jnp.where(picks == first + j, weights, 0.0), axis=-1)
        out = out + w[:, None] * relu2_ffn(
            x, p[prefix + "up_weight"][j], p[prefix + "down_weight"][j])
    return out


def forward(params, aux, ids, cfg, loads=None):
    """Logits (batch x positions, vocabulary rows) in float32; each expert
    layer's picks an expert appended to ``loads`` where it is a list."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    eps = cfg["layer_norm_epsilon"]
    batch, t = ids.shape
    x = p["embed_weight"][ids].reshape(batch * t, -1)
    # DEPARTURE (the cut): the first num_hidden_layers layers of the 52,
    # the held experts of each, the first vocab_size rows of the vocabulary
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    for l, kind in enumerate(pattern):
        pre = f"layer{l}_"
        n = rms_norm(x, p[pre + "norm_gamma"], eps)
        if kind == "M":
            x = x + mamba_mixer(n, p, pre + "mamba_", cfg, batch, t)
        elif kind == "E":
            # DEPARTURE (assumed): the selection bias is the one training
            # left in ``aux``, from a drawn start at zeros, where a
            # published checkpoint holds its trained e_score_correction_bias
            x = x + sparse_ffn(n, p, pre + "moe_", jnp.asarray(
                aux[pre + "moe_selection_bias"], jnp.float32), cfg, loads)
        else:
            x = x + grouped_query_attention(n, p, pre, cfg, batch, t)
    return rms_norm(x, p["final_norm_gamma"], eps) @ p["head_weight"].T


def logits(params, aux, ids, config=None):
    """The harness's check: ``aux`` gives the selection biases (the pick
    counts and the scan's records play no part)."""
    return forward(params, aux, jnp.asarray(ids, jnp.int32),
                   config or file_config())


def loss_and_grads(params, aux, ids, labels, config=None):
    """Summed cross-entropy of every position's next token, and its
    gradient by every parameter (``fit`` divides by the batch's rows)."""
    cfg = config or file_config()
    ids = jnp.asarray(ids, jnp.int32)
    labels = jnp.asarray(labels, jnp.int32).reshape(-1)

    def loss(p):
        logp = jax.nn.log_softmax(forward(p, aux, ids, cfg), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1))

    return jax.value_and_grad(loss)(
        {k: jnp.asarray(v, jnp.float32) for k, v in params.items()})


def bias_step(params, aux, ids, rate, config=None):
    """``aux`` with every selection bias moved as a training step on
    ``ids`` moves it: ``b_e + rate * sign(mean load - load_e)``, the loads
    the step's picks on all experts."""
    cfg = config or file_config()
    loads = []
    forward(params, aux, jnp.asarray(ids, jnp.int32), cfg, loads)
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    out = dict(aux)
    for l, load in zip([l for l, kind in enumerate(pattern) if kind == "E"],
                       loads):
        name = f"layer{l}_moe_selection_bias"
        out[name] = np.asarray(aux[name] + rate * jnp.sign(
            jnp.mean(load) - load), np.float32)
    return out
