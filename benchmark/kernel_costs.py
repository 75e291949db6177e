"""What a kernel of the decoder cells must do, from its shapes: operations
by ``flops.py``'s counts (a causal or windowed kernel is not credited with
the products it skips; backward = 2 x forward; nothing recomputed counts)
and bytes as every tensor it must read and write, once. Copies of what the
program's kernels compute (``mxnet_tpu/ops/pallas/flash_attention.py``, the
grouped product ``jax.lax.ragged_dot`` under ``MixtureOfExperts``), kept
with the benchmark so that a PR that changes a kernel cannot change its
yardstick. ``roofline_seconds`` is the least time the chip could take.
"""


def kv_mean(q_len, window):
    """Mean keys a query attends (``flops.py``'s ``attention``)."""
    if not window or window >= q_len:
        return (q_len + 1) / 2
    return window - window * (window - 1) / (2 * q_len)


def flash_attention(heads, kv_heads, q_len, qk_dim, v_dim, window,
                    itemsize=2):
    """``(forward, backward)``, each ``{"flops", "bytes"}``, of one causal
    grouped-query attention over one sequence whose queries and keys are
    ``qk_dim`` wide and whose values and outputs ``v_dim`` (``flops.py``'s
    ``attention`` layer: the two sizes differ under latent attention).
    Forward reads q, k, v and writes o and the row statistics; backward
    reads q, k, v, o, do and the statistics and writes dq, dk, dv."""
    q = heads * q_len * qk_dim * itemsize
    o = heads * q_len * v_dim * itemsize
    k = kv_heads * q_len * qk_dim * itemsize
    v = kv_heads * q_len * v_dim * itemsize
    stats = heads * q_len * 4
    forward_flops = 2 * (heads * q_len * kv_mean(q_len, window)
                         * (qk_dim + v_dim))
    return ({"flops": forward_flops, "bytes": q + k + v + o + stats},
            {"flops": 2 * forward_flops,
             "bytes": 2 * (q + k + v + o) + 2 * stats})


def grouped_product(rows, cin, cout, experts, itemsize=2, out_itemsize=4):
    """One grouped product: ``rows`` rows of ``cin`` against the ``(cin,
    cout)`` matrix of the expert each belongs to, of ``experts`` held."""
    return {"flops": 2 * rows * cin * cout,
            "bytes": rows * cin * itemsize + experts * cin * cout * itemsize
            + rows * cout * out_itemsize}


def gated_experts(rows, hidden, width, experts):
    """``(forward, backward)`` lists of the grouped products of one layer
    of gated experts at ``rows`` routed rows: gate, up and down forward;
    for each of them a product for the rows' gradient and one for the
    weights' gradient backward (the weights' gradient reads both row
    operands and writes float32 matrices)."""
    forward = [grouped_product(rows, hidden, width, experts),
               grouped_product(rows, hidden, width, experts),
               grouped_product(rows, width, hidden, experts)]
    backward = []
    for cin, cout in ((hidden, width), (hidden, width), (width, hidden)):
        backward.append(grouped_product(rows, cout, cin, experts))
        backward.append({"flops": 2 * rows * cin * cout,
                         "bytes": rows * (cin + cout) * 2
                         + experts * cin * cout * 4})
    return forward, backward


def roofline_seconds(cost, peak):
    """The larger of operations over the bf16 peak and bytes over the
    memory bandwidth (``peaks.json``)."""
    return max(cost["flops"] / peak["bf16_flops"],
               cost["bytes"] / peak["hbm_bytes_per_s"])
