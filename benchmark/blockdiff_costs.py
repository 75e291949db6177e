"""What the attention kernels of block-diffusion training must do, from
their shapes, by the rules of ``kernel_costs.py``: operations by
``flops.py``'s counts (the products behind the mask are not credited;
backward = 2 x forward; nothing recomputed counts) and bytes as every
tensor the kernels must read and write, once. A copy of what
``mxnet_tpu/ops/pallas/flash_attention.py`` computes with ``step=B,
halves=2``, kept with the benchmark so that a PR that changes the kernel
cannot change its yardstick. ``SCOPE`` is where the executor emits the
operator in the train program's HLO (``scopes.py``), forward ``jvp(...)``,
backward and the recomputed forward ``transpose(jvp(...))``.
"""

SCOPE = r"layer\d+_attn/BlockDiffusionAttention"


def kv_mean(seq_len, block):
    """Mean keys a query attends (``walkers/BlockDiffusionAttention.py``):
    a clean query the clean keys through the end of its block, a noisy one
    the clean keys before its block and its own block's noisy keys."""
    return (seq_len + block) / 2


def flash_attention(heads, kv_heads, seq_len, block, qk_dim, v_dim,
                    itemsize=2):
    """``(forward, backward)``, each ``{"flops", "bytes"}``, of one
    grouped-query attention over one sequence's noisy and clean copy (``2 x
    seq_len`` rows of queries, keys and values). Forward reads q, k, v and
    writes o and the row statistics; backward reads q, k, v, o, do and the
    statistics and writes dq, dk, dv."""
    rows = 2 * seq_len
    q = heads * rows * qk_dim * itemsize
    o = heads * rows * v_dim * itemsize
    k = kv_heads * rows * qk_dim * itemsize
    v = kv_heads * rows * v_dim * itemsize
    stats = heads * rows * 4
    forward_flops = 2 * (heads * rows * kv_mean(seq_len, block)
                         * (qk_dim + v_dim))
    return ({"flops": forward_flops, "bytes": q + k + v + o + stats},
            {"flops": 2 * forward_flops,
             "bytes": 2 * (q + k + v + o) + 2 * stats})
