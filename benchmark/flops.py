"""Model FLOP per sample, counted from the configuration's layer shapes.

The algorithm's count, not XLA's cost model: the products only
(convolution, fully-connected, a product taken at every position of a
sequence, attention's two products), 2 FLOP a multiply-add (as the
published peak of the chip counts them), backward = 2 x forward (one
product for the input gradient, one for the weight gradient; for
attention two for each of its two products). Normalisation, pooling,
activations, softmax, gathers, the loss and the optimizer are not counted,
and nothing recomputed would be.

A configuration file lists its layers under ``flops_per_sample.layers``:
  {"op": "conv", "name": ..., "kernel": [kh, kw], "cin": c, "cout": k,
   "out": [oh, ow]}      -> 2 * kh * kw * c * k * oh * ow   forward
  {"op": "fc", "name": ..., "cin": c, "cout": k}  -> 2 * c * k  forward
  {"op": "matmul", "name": ..., "cin": c, "cout": k, "rows": r}
                         -> 2 * c * k * r   forward: one product taken at
      ``r`` positions of one sample. ``r`` may be fractional: the expected
      number of routed picks that one chip's experts serve.
  {"op": "attention", "name": ..., "heads": h, "qk_dim": dq, "v_dim": dv,
   "q_len": T, "kv_mean": m}  -> 2 * h * T * m * (dq + dv)   forward:
      scores (dq a key) and the weighted sum of values (dv a key), at the
      keys a query attends and no others. ``m`` is the mean number of keys
      a query attends:
        causal over T                (T + 1) / 2
        causal, window W <= T        W - W * (W - 1) / (2 * T)
      (query i of 0..T-1 sees min(i + 1, W) keys: the first W queries see
      1..W, sum W (W + 1) / 2, the other T - W see W each.)
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
    elif layer["op"] == "matmul":
        macs = layer["cin"] * layer["cout"] * layer["rows"]
    elif layer["op"] == "attention":
        macs = layer["heads"] * layer["q_len"] * layer["kv_mean"] \
            * (layer["qk_dim"] + layer["v_dim"])
    else:
        raise ValueError(f"flops: unknown layer op {layer['op']!r}")
    return FLOP_PER_MAC * macs


def forward_flops_per_sample(layers):
    return sum(layer_forward_flops(layer) for layer in layers)


def train_flops_per_sample(layers):
    """Forward + backward."""
    return (1 + BACKWARD_OVER_FORWARD) * forward_flops_per_sample(layers)
