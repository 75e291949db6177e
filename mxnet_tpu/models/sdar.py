"""SDAR-30B-A3B-Chat (JetLM, ``model_type: sdar_moe``, 30B-A3B): a pre-norm
sparse decoder trained by block diffusion, as a Symbol through
``FeedForward.fit`` like every other model of the zoo.

Source: https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json.
Every published size is an argument that defaults to the config's value.
What one rank of an expert-parallel deployment holds is given by the cut:
``layers`` (the first so many of the 48), ``experts_held`` / ``first_expert``
(its experts of every layer; the router keeps all ``num_experts`` outputs
and its top ``num_experts_per_tok``), ``vocab_rows`` (its rows of the
embedding and the head: ids, logits and loss are over the slice).

Layer ``l``: ``h = x + W_o Attn(q, k, v)``, ``y = h + MoE(RMSNorm(h))``
with ``n = RMSNorm(x)``, ``q = RMSNorm_head(W_q n)``, ``k =
RMSNorm_head(W_k n)`` (one learnable vector of ``head_dim`` for all query
heads and one for all key heads, applied a head before the rotation), ``v =
W_v n``; rotary positions on the whole head. ``MoE`` is the top-k of a
softmax over all experts, the picked probabilities divided by their sum
(``norm_topk_prob``), no shared expert. No biases; final RMSNorm, untied
head.

Block-diffusion training (the BD3-LM construction, Arriola et al.,
arXiv:2503.09573): ``data`` (batch, 2 * seq_len) is a sequence's noisy copy
``xt`` (in every block of ``block_length`` some positions replaced by
``mask_id``) followed by its clean copy ``x0``; the model runs ONCE on the
``2 * seq_len`` rows under ``BlockDiffusionAttention``'s mask; the head and
the loss (``MaskedDiffusionOutput``: the masked rows, a block's at ``1 /
its masked share``) run on the ``seq_len`` noisy rows, whose label
``softmax_label`` (batch, seq_len) is ``x0``. The clean rows' last-layer
output is not used; their keys and values in every layer are.

``train_router`` defaults to whether every expert is held here, as in
``laguna``; with ``remat`` every decoder layer ends in a ``RematBoundary``.
What the config does not give (the per-head norms, the block length, the
noise schedule) is listed under ``assumed`` in
benchmark/configs/sdar_30b_a3b.json.
"""

from .. import symbol as sym
from .laguna import _linear


def sdar(seq_len=4096, block_length=4, mask_id=None, layers=48,
         vocab_rows=151936, experts_held=128, first_expert=0,
         hidden_size=2048, head_dim=128, num_attention_heads=32,
         num_key_value_heads=4, num_experts=128, num_experts_per_tok=8,
         moe_intermediate_size=768, rope_theta=1000000.0, rms_norm_eps=1e-6,
         remat=True, train_router=None):
    """The decoder's Symbol: ids ``data`` (batch, 2 * seq_len), the noisy
    copy then the clean one, and clean ids ``softmax_label`` (batch,
    seq_len) in; softmax over ``vocab_rows`` on the (batch * seq_len) noisy
    rows out; the logits are ``head_output``. ``mask_id`` defaults to the
    last row of the vocabulary held here."""
    if mask_id is None:
        mask_id = vocab_rows - 1
    if train_router is None:
        train_router = experts_held == num_experts
    ids = sym.Variable("data")
    x = sym.Reshape(
        data=sym.Embedding(data=ids, input_dim=vocab_rows,
                           output_dim=hidden_size, name="embed"),
        target_shape=(-1, hidden_size), name="embed_rows")

    def head_norm(rows, heads, name):
        # one scale of head_dim for every head: the heads as rows
        return sym.Reshape(
            data=sym.RMSNorm(
                data=sym.Reshape(data=rows, target_shape=(-1, head_dim),
                                 name=f"{name}_heads"),
                eps=rms_norm_eps, name=f"{name}_norm"),
            target_shape=(-1, heads * head_dim), name=f"{name}_normed")

    for l in range(layers):
        name = f"layer{l}"
        n1 = sym.RMSNorm(data=x, eps=rms_norm_eps, name=f"{name}_attn_norm")
        attn = sym.BlockDiffusionAttention(
            name=f"{name}_attn", seq_len=seq_len, block_length=block_length,
            num_heads=num_attention_heads, num_kv_heads=num_key_value_heads,
            head_dim=head_dim, rotary_dim=head_dim,
            rope_theta=float(rope_theta),
            query=head_norm(_linear(n1, num_attention_heads * head_dim,
                                    f"{name}_q"),
                            num_attention_heads, f"{name}_q"),
            key=head_norm(_linear(n1, num_key_value_heads * head_dim,
                                  f"{name}_k"),
                          num_key_value_heads, f"{name}_k"),
            value=_linear(n1, num_key_value_heads * head_dim, f"{name}_v"))
        h = sym._Plus(lhs=x, rhs=_linear(attn, hidden_size, f"{name}_o"),
                      name=f"{name}_attn_add")
        n2 = sym.RMSNorm(data=h, eps=rms_norm_eps, name=f"{name}_ffn_norm")
        ffn = sym.MixtureOfExperts(
            data=n2, name=f"{name}_moe", num_experts=num_experts,
            experts_held=experts_held, first_expert=first_expert,
            top_k=num_experts_per_tok, expert_width=moe_intermediate_size,
            score="softmax", train_router=bool(train_router))
        x = sym._Plus(lhs=h, rhs=ffn, name=f"{name}_ffn_add")
        if remat:
            x = sym.RematBoundary(data=x, name=f"{name}_out")

    def noisy_half(data, name):
        # of every sequence's 2 * seq_len entries along axis 1 the first
        # seq_len
        return sym.SliceChannel(data=data, num_outputs=2, axis=1,
                                name=name)[0]

    noisy = sym.Reshape(
        data=noisy_half(sym.Reshape(
            data=x, target_shape=(-1, 2 * seq_len, hidden_size),
            name="row_pairs"), "row_halves"),
        target_shape=(-1, hidden_size), name="noisy_rows")
    head = _linear(sym.RMSNorm(data=noisy, eps=rms_norm_eps,
                               name="final_norm"), vocab_rows, "head")
    return sym.MaskedDiffusionOutput(
        data=head, name="softmax", label=sym.Variable("softmax_label"),
        noisy=noisy_half(ids, "id_halves"), mask_id=mask_id,
        block_length=block_length)
