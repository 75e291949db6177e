"""How ``sdar_30b_a3b``'s drawn weights were chosen: a forward-only sweep at
the cell's size, on the chip, over the values that the per-head norm scales
on queries and keys start at (where both are ``g`` the attention scores are
``g * g`` times the unit-scale model's). For each seed it draws the weights
as the cell's initializer does with those scales at one, takes the cell's
first batch, and for each of ``--fills`` (a name, and the value that the
parameters matching a regular expression are filled with; the first
expression that matches, as in ``mx.init.Mixed``) prints one JSON line:

    reference_bf16    the witness (``sdar_30b_a3b_control_bf16.py``): how
                      far the MODEL moves when its products are bfloat16
    e4m3, causal      the two controls
                      (each a relative L2 error against the float32
                      reference, ``checks.relative_error``)
    picks_per_token   this rank's picks a token, a layer; 1.0 is its share
    max_over_mean     the fullest held expert over the mean, a layer
                      (the first seed gets every column, the others the
                      e4m3 control and the load)

It reads the model, not the program: nothing trains (the cell's 200 steps
at 1e-5 hardly move a weight) and the program's own distance from the
reference is the runner's to read, on the trained weights, for the fill the
configuration's file then states. Run from the root of a checkout:

    python3 benchmark/configs/sdar_30b_a3b_sweep.py [--seeds 1,2,3]
        [--fills '{"g2": {"_[qk]_norm_gamma$": 2}}'] [--rehearse-on-cpu]
"""

import argparse
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import catalog  # noqa: E402
import checks  # noqa: E402

CELL = "sdar_30b_a3b.blockdiff4k"
HEAD_NORMS = "_[qk]_norm_gamma$"
FILLS = {f"g{g}": {HEAD_NORMS: g} for g in (1, 1.5, 2, 2.5, 3)}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", default="3300000501,3300000502,3300000503")
    p.add_argument("--fills", type=json.loads, default=FILLS)
    p.add_argument("--rehearse-on-cpu", action="store_true")
    args = p.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx

    platform = "cpu" if args.rehearse_on_cpu else "tpu"
    device = jax.devices()[0]
    if device.platform != platform:
        sys.exit(f"sweep: first device is {device}, not a {platform}")
    found = catalog.find_cell(catalog.load_benchmark(), CELL)
    config, traffic = found["config"], found["traffic"]
    symbol = catalog.build_symbol(config["builder"], HERE)

    def load(name):
        return catalog.load_file_module(os.path.join(HERE, name),
                                        "sweep_" + name[:-3])

    plain = load(config["reference"])
    loads = []
    inner = plain.sparse_ffn

    def spy(x, p, prefix, cfg):
        # this rank's picks an expert, read beside the reference's own
        _, picks = jax.lax.top_k(x @ p[prefix + "router_weight"].T,
                                 cfg["num_experts_per_tok"])
        loads.append(jnp.sum(jax.nn.one_hot(
            picks - cfg.get("first_expert", 0), cfg["num_experts"],
            dtype=jnp.int32), axis=(0, 1)))
        return inner(x, p, prefix, cfg)

    plain.sparse_ffn = spy

    def logits_and_load(params, ids):
        del loads[:]
        return plain.logits(params, None, ids), jnp.stack(loads)

    references = {"want": jax.jit(logits_and_load)}
    for name in ("bf16", "e4m3", "causal"):
        references[name] = jax.jit(
            load(f"sdar_30b_a3b_control_{name}.py").logits)

    shapes = {"data": (1, int(config["input_shape"][0])),
              "softmax_label": (1, int(config["input_shape"][0]) // 2)}
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        seed %= 2 ** 31 - 1
        mx.random.seed(seed)
        np.random.seed(seed)
        model = mx.FeedForward(symbol, ctx=mx.Context(platform, device.id),
                               initializer=mx.init.Xavier())
        model._init_params(shapes)
        drawn = {k: v.asnumpy() for k, v in model.arg_params.items()}
        feed = catalog.load_feeder(traffic["kind"]).make(
            traffic, config, [device], seed, "data", "softmax_label")
        ids = feed.check_rows(1)
        del feed, model
        for fill, values in args.fills.items():
            filled = {name: next(
                (np.full_like(arr, value) for pattern, value in
                 values.items() if re.search(pattern, name)), arr)
                for name, arr in drawn.items()}
            params = jax.device_put(filled, device)
            with jax.default_device(device), \
                    jax.default_matmul_precision("highest"):
                want, load_ = references["want"](params, ids)
                want = np.asarray(want).reshape(1, -1)
                load_ = np.asarray(load_, np.float64)
                line = {"seed": seed, "fill": fill,
                        "picks_per_token": [round(float(v), 4) for v in
                                            load_.sum(1) / ids.shape[1]],
                        "max_over_mean": [round(float(v), 3) for v in
                                          load_.max(1) / load_.mean(1)]}
                for name in ("bf16", "e4m3", "causal"):
                    if n == 0 or name == "e4m3":    # the first seed: all
                        key = "reference_bf16" if name == "bf16" else name
                        line[key] = checks.relative_error(
                            np.asarray(references[name](params, None, ids))
                            .reshape(1, -1), want)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
