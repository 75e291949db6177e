"""``RotaryAttention``: one ``attention`` entry, the two products at the
keys a query attends and no others: causal, ``(T + 1) / 2`` keys a query
in the mean; with a window of ``W`` keys, ``W - W (W - 1) / (2 T)``. The
projections around it are ``FullyConnected`` nodes with entries of their
own; rotary positions and the gate are elementwise."""


def layers(node, in_shapes, out_shapes):
    p = node["param"]
    t, w = int(p["seq_len"]), int(p["window"])
    kv_mean = (t + 1) / 2 if not w or w >= t else w - w * (w - 1) / (2 * t)
    return [{"op": "attention", "name": node["name"],
             "heads": int(p["num_heads"]), "qk_dim": int(p["head_dim"]),
             "v_dim": int(p["head_dim"]), "q_len": t, "kv_mean": kv_mean}]
