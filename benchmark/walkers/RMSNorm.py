"""``RMSNorm``: a learnable scale, no product: no layer."""


def layers(node, in_shapes, out_shapes):
    return []
