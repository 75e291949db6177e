"""Model FLOP per sample, counted from the configuration's layer shapes.

The algorithm's count, not XLA's cost model: convolution and
fully-connected layers only, 2 FLOP a multiply-add (as the published peak
of the chip counts them), backward = 2 x forward (one product for the
input gradient, one for the weight gradient). BatchNorm, pooling,
activations, the loss and the optimizer are not counted, and nothing
recomputed would be.

A configuration file lists its layers under ``flops_per_sample.layers``:
  {"op": "conv", "name": ..., "kernel": [kh, kw], "cin": c, "cout": k,
   "out": [oh, ow]}      -> 2 * kh * kw * c * k * oh * ow   forward
  {"op": "fc", "name": ..., "cin": c, "cout": k}  -> 2 * c * k  forward
"""

from __future__ import annotations

FLOP_PER_MAC = 2
BACKWARD_OVER_FORWARD = 2


def layer_forward_flops(layer):
    if layer["op"] == "conv":
        kh, kw = layer["kernel"]
        oh, ow = layer["out"]
        macs = kh * kw * layer["cin"] * layer["cout"] * oh * ow
    elif layer["op"] == "fc":
        macs = layer["cin"] * layer["cout"]
    else:
        raise ValueError(f"flops: unknown layer op {layer['op']!r}")
    return FLOP_PER_MAC * macs


def forward_flops_per_sample(layers):
    return sum(layer_forward_flops(layer) for layer in layers)


def train_flops_per_sample(layers):
    """Forward + backward."""
    return (1 + BACKWARD_OVER_FORWARD) * forward_flops_per_sample(layers)
