"""``Mamba2Mixer``: ``matmul`` entries for the causal convolution (every
position of every channel against its ``conv_kernel`` taps) and the four
products of the chunked scan (``ssm_costs.py``): the scores ``C B^T``
within a chunk a group, the weighted sum ``(L o C B^T) X`` a head, each
chunk's own state ``B^T X`` and the carried states' part ``C S``. The two
causal products within a chunk are credited at the ``(chunk + 1) / 2``
positions a position reads in the mean, as ``flops.py`` credits attention.
The projections around it are ``FullyConnected`` nodes with walkers of
their own; the softplus, the decays, the carry from chunk to chunk and the
gated norm are elementwise."""

import os
import runpy

COSTS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "ssm_costs.py"))


def layers(node, in_shapes, out_shapes):
    p, name = node["param"], node["name"]
    rows, channels = in_shapes[node["args"].index("xbc")]
    out = [{"op": "matmul", "name": f"{name}_conv",
            "cin": int(p["conv_kernel"]), "cout": 1,
            "rows": rows * channels}]
    return out + [
        {"op": "matmul", "name": f"{name}_{part}", "cin": cin, "cout": cout,
         "rows": at}
        for part, cin, cout, at in COSTS["products"](
            rows, int(p["num_heads"]), int(p["head_dim"]),
            int(p["n_groups"]), int(p["state_size"]),
            int(p["chunk_size"]))]
