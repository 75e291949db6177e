"""Laguna-XS.2 (poolside, ``model_type: laguna``, 33.4B-A3B): a pre-norm
sparse decoder as a Symbol, trained through ``FeedForward.fit`` like every
other model of the zoo.

Source: https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json.
Every published size is an argument that defaults to the config's value.
What one rank of an expert-parallel deployment holds is given by the cut:
``layers`` (the first so many of the 40), ``experts_held`` / ``first_expert``
(its experts of every sparse layer; the router keeps all ``num_experts``
outputs and its top ``num_experts_per_tok``), ``vocab_rows`` (its rows of
the embedding and the head: ids, logits and loss are over the slice).

Layer ``l``: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``;
attention is grouped-query (``num_attention_heads_per_layer[l]`` query
heads over ``num_key_value_heads``), causal, with a window of
``sliding_window`` keys on ``sliding_attention`` layers, rotary positions
by ``rope_parameters[layer type]`` and a sigmoid gate a head taken from the
layer's normalised input (``gating``). The FFN is a gated SiLU product,
dense of ``intermediate_size`` or (``mlp_layer_types[l] == "sparse"``) the
top-k of ``num_experts`` experts of ``moe_intermediate_size`` plus a shared
expert. No biases; embedding and head untied. Forms the config does not
spell out (the gate's granularity, the router's score function, the rotary
pairing) are listed under ``assumed`` in benchmark/configs/laguna_xs2.json.

``train_router`` defaults to whether every expert is held here: a cut rank
that trains alone keeps its router as it is (``MixtureOfExperts``).

With ``remat`` every decoder layer ends in a ``RematBoundary``: the
executor recomputes a layer's interior in the backward pass and keeps only
the residual stream between layers.
"""

from .. import symbol as sym

_FULL, _SLIDING = "full_attention", "sliding_attention"

ROPE_PARAMETERS = {
    _FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
    _SLIDING: {"rope_type": "default", "rope_theta": 10000,
               "partial_rotary_factor": 1},
}


def _rotary_kwargs(rope, head_dim):
    kwargs = {
        "rotary_dim": int(head_dim * rope.get("partial_rotary_factor", 1)),
        "rope_theta": float(rope["rope_theta"]),
        "rope_type": rope.get("rope_type", "default"),
    }
    if kwargs["rope_type"] == "yarn":
        kwargs.update(
            rope_factor=float(rope["factor"]),
            rope_original_max_position=int(
                rope["original_max_position_embeddings"]),
            rope_beta_fast=float(rope["beta_fast"]),
            rope_beta_slow=float(rope["beta_slow"]),
            rope_attention_factor=float(rope["attention_factor"]))
    return kwargs


def _linear(data, width, name):
    return sym.FullyConnected(data=data, num_hidden=width, no_bias=True,
                              name=name)


def laguna(seq_len=8192, layers=40, vocab_rows=100352, experts_held=256,
           first_expert=0, hidden_size=2048, intermediate_size=8192,
           head_dim=128, num_key_value_heads=8,
           num_attention_heads_per_layer=(48, 64, 64, 64) * 10,
           layer_types=(_FULL, _SLIDING, _SLIDING, _SLIDING) * 10,
           mlp_layer_types=("dense",) + ("sparse",) * 39,
           sliding_window=512, num_experts=256, num_experts_per_tok=8,
           moe_intermediate_size=512, shared_expert_intermediate_size=512,
           moe_routed_scaling_factor=2.5, rms_norm_eps=1e-6, gating=True,
           rope_parameters=None, remat=True, train_router=None):
    """The decoder's Symbol: ids ``data`` (batch, seq_len) and next-token
    labels ``softmax_label`` (batch, seq_len) in, softmax over ``vocab_rows``
    on (batch * seq_len) rows out; the logits are ``head_output``."""
    rope_parameters = rope_parameters or ROPE_PARAMETERS
    if train_router is None:
        # a rank that holds a share and trains alone sees only its own
        # experts' part of the router's gradient (MixtureOfExperts)
        train_router = experts_held == num_experts
    x = sym.Reshape(
        data=sym.Embedding(data=sym.Variable("data"), input_dim=vocab_rows,
                           output_dim=hidden_size, name="embed"),
        target_shape=(-1, hidden_size), name="embed_rows")
    for l in range(layers):
        name = f"layer{l}"
        heads = num_attention_heads_per_layer[l]
        sliding = layer_types[l] == _SLIDING
        n1 = sym.RMSNorm(data=x, eps=rms_norm_eps, name=f"{name}_attn_norm")
        inputs = {
            "query": _linear(n1, heads * head_dim, f"{name}_q"),
            "key": _linear(n1, num_key_value_heads * head_dim, f"{name}_k"),
            "value": _linear(n1, num_key_value_heads * head_dim,
                             f"{name}_v"),
        }
        if gating:
            inputs["gate"] = _linear(n1, heads, f"{name}_gate")
        attn = sym.RotaryAttention(
            name=f"{name}_attn", seq_len=seq_len, num_heads=heads,
            num_kv_heads=num_key_value_heads, head_dim=head_dim,
            window=sliding_window if sliding else 0, gated=bool(gating),
            **_rotary_kwargs(rope_parameters[layer_types[l]], head_dim),
            **inputs)
        h = sym._Plus(lhs=x, rhs=_linear(attn, hidden_size, f"{name}_o"),
                      name=f"{name}_attn_add")
        n2 = sym.RMSNorm(data=h, eps=rms_norm_eps, name=f"{name}_ffn_norm")
        if mlp_layer_types[l] == "sparse":
            ffn = sym.MixtureOfExperts(
                data=n2, name=f"{name}_moe", num_experts=num_experts,
                experts_held=experts_held, first_expert=first_expert,
                top_k=num_experts_per_tok,
                expert_width=moe_intermediate_size,
                scaling=moe_routed_scaling_factor,
                shared_width=shared_expert_intermediate_size,
                train_router=bool(train_router))
        else:
            act = sym.Activation(
                data=_linear(n2, intermediate_size, f"{name}_ffn_gate"),
                act_type="silu", name=f"{name}_ffn_act")
            ffn = _linear(
                sym._Mul(lhs=act,
                         rhs=_linear(n2, intermediate_size,
                                     f"{name}_ffn_up"),
                         name=f"{name}_ffn_mul"),
                hidden_size, f"{name}_ffn_down")
        x = sym._Plus(lhs=h, rhs=ffn, name=f"{name}_ffn_add")
        if remat:
            x = sym.RematBoundary(data=x, name=f"{name}_out")
    head = _linear(sym.RMSNorm(data=x, eps=rms_norm_eps, name="final_norm"),
                   vocab_rows, "head")
    return sym.SoftmaxOutput(
        data=head, name="softmax",
        label=sym.Reshape(data=sym.Variable("softmax_label"),
                          target_shape=(-1,), name="label_rows"))
