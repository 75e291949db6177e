"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type: nemotron_h``, 31.6B-A3.2B):
a hybrid decoder of Mamba-2 state-space layers, sparse expert layers and a
few attention layers as a Symbol, trained through ``FeedForward.fit`` like
every other model of the zoo.

Source: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/
blob/main/config.json. Every published size is an argument that defaults
to the config's value, and ``hybrid_override_pattern`` is taken as
published: layer ``l`` is a Mamba-2 layer (``M``), an expert layer
(``E``) or an attention layer (``*``) as its letter says. What one rank
of an expert-parallel deployment holds is given by the cut: ``layers``
(the first so many of the pattern's), ``experts_held`` / ``first_expert``
(its experts of every expert layer; the router keeps all
``n_routed_experts`` outputs and its top ``num_experts_per_tok``),
``vocab_rows`` (its rows of the embedding and the head: ids, logits and
loss are over the slice).

Every block is ``x + Mixer(RMSNorm(x))``, one mixer and no other branch:

- ``M``: ``[z | xBC | dt] = W_in n`` (one projection, its columns split),
  ``Mamba2Mixer`` (the causal convolution, the chunked scan, the gated
  norm by ``n_groups`` groups), ``W_out``; nodes ``layer<l>_mamba*``.
- ``E``: ``MixtureOfExpertsSquaredReLU``: sigmoid scores over all
  experts, the top ``num_experts_per_tok`` of the scores plus a selection
  bias moved by the load, weighed by the scores alone, normalised and
  scaled by ``routed_scaling_factor``; experts ``down(relu(up n)^2)`` of
  ``moe_intermediate_size`` and one shared expert of
  ``moe_shared_expert_intermediate_size``; node ``layer<l>_moe``.
- ``*``: causal grouped-query attention, no rotary positions
  (``RotaryAttention(rotary_dim=0)``), ``W_o``; node ``layer<l>_attn``.

The stack ends in an RMSNorm and an untied head. No biases but the
convolution's. The initial values of ``A_log``, ``D`` and ``dt_bias`` are
the initializer's (``mx.init.Mixed`` with ``SSMDecay`` / ``SSMStepBias``),
not the builder's. ``train_router`` defaults to whether every expert is
held here (as ``kanana2``); with ``remat`` every block ends in a
``RematBoundary``.
"""

from .. import symbol as sym
from .decoder_parts import columns, linear

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def nemotron_h(seq_len=8192, layers=52, vocab_rows=131072, experts_held=128,
               first_expert=0, hidden_size=2688,
               hybrid_override_pattern=PATTERN, mamba_num_heads=64,
               mamba_head_dim=64, n_groups=8, ssm_state_size=128,
               conv_kernel=4, chunk_size=128, n_routed_experts=128,
               num_experts_per_tok=6, moe_intermediate_size=1856,
               moe_shared_expert_intermediate_size=3712, n_shared_experts=1,
               routed_scaling_factor=2.5, num_attention_heads=32,
               num_key_value_heads=2, head_dim=128, layer_norm_epsilon=1e-5,
               remat=True, train_router=None):
    """The decoder's Symbol: ids ``data`` (batch, seq_len) and next-token
    labels ``softmax_label`` (batch, seq_len) in, softmax over ``vocab_rows``
    on (batch * seq_len) rows out; the logits are ``head_output``."""
    if train_router is None:
        # a rank that holds a share and trains alone sees only its own
        # experts' part of the router's gradient (MixtureOfExperts)
        train_router = experts_held == n_routed_experts
    eps = layer_norm_epsilon
    inner = mamba_num_heads * mamba_head_dim
    x = sym.Reshape(
        data=sym.Embedding(data=sym.Variable("data"), input_dim=vocab_rows,
                           output_dim=hidden_size, name="embed"),
        target_shape=(-1, hidden_size), name="embed_rows")
    for l, kind in enumerate(hybrid_override_pattern[:layers]):
        name = f"layer{l}"
        n = sym.RMSNorm(data=x, eps=eps, name=f"{name}_norm")
        if kind == "M":
            z, xbc, dt = columns(
                linear(n, 2 * inner + 2 * n_groups * ssm_state_size
                       + mamba_num_heads, f"{name}_mamba_in"),
                {"z": inner, "xbc": inner + 2 * n_groups * ssm_state_size,
                 "dt": mamba_num_heads}, f"{name}_mamba_in_split")
            mixed = linear(sym.Mamba2Mixer(
                xbc=xbc, z=z, dt=dt, name=f"{name}_mamba", seq_len=seq_len,
                num_heads=mamba_num_heads, head_dim=mamba_head_dim,
                n_groups=n_groups, state_size=ssm_state_size,
                conv_kernel=conv_kernel, chunk_size=chunk_size, eps=eps),
                hidden_size, f"{name}_mamba_out")
        elif kind == "E":
            mixed = sym.MixtureOfExpertsSquaredReLU(
                data=n, name=f"{name}_moe", num_experts=n_routed_experts,
                experts_held=experts_held, first_expert=first_expert,
                top_k=num_experts_per_tok, expert_width=moe_intermediate_size,
                scaling=routed_scaling_factor,
                shared_width=n_shared_experts
                * moe_shared_expert_intermediate_size,
                selection="biased", train_router=bool(train_router))
        elif kind == "*":
            attn = sym.RotaryAttention(
                name=f"{name}_attn", seq_len=seq_len,
                num_heads=num_attention_heads,
                num_kv_heads=num_key_value_heads, head_dim=head_dim,
                rotary_dim=0,
                query=linear(n, num_attention_heads * head_dim, f"{name}_q"),
                key=linear(n, num_key_value_heads * head_dim, f"{name}_k"),
                value=linear(n, num_key_value_heads * head_dim, f"{name}_v"))
            mixed = linear(attn, hidden_size, f"{name}_o")
        else:
            raise ValueError(f"nemotron_h: layer {l} is {kind!r}; the "
                             "pattern's letters are M, E and *")
        x = sym._Plus(lhs=x, rhs=mixed, name=f"{name}_add")
        if remat:
            x = sym.RematBoundary(data=x, name=f"{name}_out")
    head = linear(sym.RMSNorm(data=x, eps=eps, name="final_norm"),
                  vocab_rows, "head")
    return sym.SoftmaxOutput(
        data=head, name="softmax",
        label=sym.Reshape(data=sym.Variable("softmax_label"),
                          target_shape=(-1,), name="label_rows"))
