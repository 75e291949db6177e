"""``BlockDiffusionAttention``: one ``attention`` entry over the ``2 T``
rows of a sequence's noisy and clean copy, the two products at the keys a
query attends and no others. With blocks of ``B``, a clean query ``i``
attends the clean keys through the end of its block, ``B (i // B) + B``;
a noisy one the ``B (i // B)`` clean keys before its block and the ``B``
noisy keys of its own: ``(T + B) / 2`` keys a query in the mean, either
way. The projections and the per-head norms around it are nodes with
walkers of their own; rotary positions are elementwise."""


def layers(node, in_shapes, out_shapes):
    p = node["param"]
    t, b = int(p["seq_len"]), int(p["block_length"])
    return [{"op": "attention", "name": node["name"],
             "heads": int(p["num_heads"]), "qk_dim": int(p["head_dim"]),
             "v_dim": int(p["head_dim"]), "q_len": 2 * t,
             "kv_mean": (t + b) / 2}]
