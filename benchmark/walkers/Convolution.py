"""``Convolution``: one ``conv`` entry. Weights are OIHW whatever the
activations' layout; ``cin`` is the weight's, so a grouped convolution
counts the products it makes."""


def layers(node, in_shapes, out_shapes):
    weight = in_shapes[node["args"].index("weight")]
    out = out_shapes[0]
    hw = out[1:3] if node["param"].get("layout", "NCHW") == "NHWC" \
        else out[2:4]
    return [{"op": "conv", "name": node["name"],
             "kernel": [weight[2], weight[3]], "cin": weight[1],
             "cout": weight[0], "out": list(hw)}]
