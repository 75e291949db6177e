"""Kanana-2-30B-A3B (kakaocorp, ``model_type: deepseek_v3``, 30B-A3B): a
pre-norm sparse decoder with multi-head latent attention as a Symbol,
trained through ``FeedForward.fit`` like every other model of the zoo.

Source: https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/
blob/main/config.json. Every published size is an argument that defaults
to the config's value. What one rank of an expert-parallel deployment
holds is given by the cut: ``layers`` (the first so many of the 48),
``experts_held`` / ``first_expert`` (its experts of every sparse layer; the
router keeps all ``n_routed_experts`` outputs and its top
``num_experts_per_tok``), ``vocab_rows`` (its rows of the embedding and the
head: ids, logits and loss are over the slice).

Layer ``l``: ``h = x + W_o LatentAttention(...)``, ``y = h +
FFN(RMSNorm(h))``. Attention (``q_lora_rank`` null): ``n = RMSNorm(x)``,
queries ``W_q n`` (a head ``[q_nope | q_pe]``), ``[c | k_pe] = W_kva n``
(one projection, its columns split), ``W_kvb RMSNorm(c)`` (a head ``[k_nope
| v]``), and ``k_pe`` one rotary key a position for every head. The first
``first_k_dense_replace`` layers have a dense gated SiLU FFN of
``intermediate_size``; the rest the top ``num_experts_per_tok`` of
``n_routed_experts`` experts of ``moe_intermediate_size``, picked by the
sigmoid scores plus a selection bias moved by the load (``topk_method:
noaux_tc``, one group) and weighed by the scores alone, normalised and
scaled by ``routed_scaling_factor``, plus one shared expert of
``n_shared_experts x moe_intermediate_size``. No biases; embedding and head
untied. Every weight is one published tensor in its published layout.

``train_router`` defaults to whether every expert is held here: a cut rank
that trains alone keeps its router as it is (``MixtureOfExperts``); the
selection bias moves either way. With ``remat`` every decoder layer ends
in a ``RematBoundary``.
"""

from .. import symbol as sym
from .decoder_parts import columns, linear as _linear


def kanana2(seq_len=8192, layers=48, vocab_rows=128256, experts_held=128,
            first_expert=0, hidden_size=2048, intermediate_size=6144,
            num_attention_heads=32, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
            n_routed_experts=128, num_experts_per_tok=6,
            moe_intermediate_size=768, n_shared_experts=2,
            routed_scaling_factor=2.448, first_k_dense_replace=1,
            rope_theta=1000000.0, rms_norm_eps=1e-6, remat=True,
            train_router=None):
    """The decoder's Symbol: ids ``data`` (batch, seq_len) and next-token
    labels ``softmax_label`` (batch, seq_len) in, softmax over ``vocab_rows``
    on (batch * seq_len) rows out; the logits are ``head_output``."""
    if train_router is None:
        # a rank that holds a share and trains alone sees only its own
        # experts' part of the router's gradient (MixtureOfExperts)
        train_router = experts_held == n_routed_experts
    heads = num_attention_heads
    x = sym.Reshape(
        data=sym.Embedding(data=sym.Variable("data"), input_dim=vocab_rows,
                           output_dim=hidden_size, name="embed"),
        target_shape=(-1, hidden_size), name="embed_rows")
    for l in range(layers):
        name = f"layer{l}"
        n1 = sym.RMSNorm(data=x, eps=rms_norm_eps, name=f"{name}_attn_norm")
        latent, key_rope = columns(
            _linear(n1, kv_lora_rank + qk_rope_head_dim, f"{name}_kv_a"),
            {"first": kv_lora_rank, "rest": qk_rope_head_dim},
            f"{name}_kv_a_split")
        attn = sym.LatentAttention(
            name=f"{name}_attn", seq_len=seq_len, num_heads=heads,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=float(rope_theta),
            query=_linear(n1, heads * (qk_nope_head_dim + qk_rope_head_dim),
                          f"{name}_q"),
            key_value=_linear(
                sym.RMSNorm(data=latent, eps=rms_norm_eps,
                            name=f"{name}_kv_norm"),
                heads * (qk_nope_head_dim + v_head_dim), f"{name}_kv_b"),
            key_rope=key_rope)
        h = sym._Plus(lhs=x, rhs=_linear(attn, hidden_size, f"{name}_o"),
                      name=f"{name}_attn_add")
        n2 = sym.RMSNorm(data=h, eps=rms_norm_eps, name=f"{name}_ffn_norm")
        if l >= first_k_dense_replace:
            ffn = sym.MixtureOfExperts(
                data=n2, name=f"{name}_moe", num_experts=n_routed_experts,
                experts_held=experts_held, first_expert=first_expert,
                top_k=num_experts_per_tok,
                expert_width=moe_intermediate_size,
                scaling=routed_scaling_factor,
                shared_width=n_shared_experts * moe_intermediate_size,
                selection="biased", train_router=bool(train_router))
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
