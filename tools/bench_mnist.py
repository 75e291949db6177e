"""On-chip MNIST train-step throughput: MLP and LeNet (BASELINE.md rows).

The reference's published MNIST anchors (example/mnist/README.md:24-26):
MLP 103K img/s and LeNet 22.5K img/s on 1x GTX 980. This measures the
same two train steps (fwd + bwd + SGD-momentum, f32 — models this small
gain nothing from bf16 and the reference trained f32) on one TPU chip.

Tiny steps are DISPATCH-bound (sub-ms kernels), so the timing runs the
whole loop in-device (lax.fori_loop over CHAINED param state, slope
between two run lengths — the bench.py/bench_flash.py convention) and
reports the per-step device time.

Writes MNIST_r<N>.json. Run: python tools/bench_mnist.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_step(model_name, batch):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.executor import _build_graph_fn
    from mxnet_tpu.models import lenet, mlp

    if model_name == "mlp":
        net = mlp()
        data_shape = (batch, 784)
    else:
        net = lenet()
        data_shape = (batch, 1, 28, 28)
    shapes = {"data": data_shape, "softmax_label": (batch,)}
    arg_shapes, _, _ = net.infer_shape(**shapes)
    rng = np.random.RandomState(0)
    params = {}
    for name, shp in zip(net.list_arguments(), arg_shapes):
        if name in shapes:
            continue
        if name.endswith("bias"):
            params[name] = jnp.zeros(shp, jnp.float32)
        else:
            scale = float(np.sqrt(2.0 / max(1, int(np.prod(shp[1:])))))
            params[name] = jnp.asarray(
                (rng.randn(*shp) * scale).astype(np.float32))
    graph_fn = _build_graph_fn(net, is_train=True)
    zero_key = jnp.zeros((2,), jnp.uint32)
    moms = {k: jnp.zeros_like(v) for k, v in params.items()}

    def step(params, moms, data, label):
        def loss_fn(p):
            outs, _ = graph_fn({**p, "data": data, "softmax_label": label},
                               {}, zero_key)
            return jnp.sum(outs[0])

        grads = jax.grad(loss_fn)(params)
        new_moms = {k: 0.9 * moms[k] + grads[k] / batch for k in params}
        new_params = {k: params[k] - 0.1 * new_moms[k] for k in params}
        return new_params, new_moms

    return step, params, moms, data_shape


def bench_model(model_name, batch, iters=50):
    import jax
    import jax.numpy as jnp

    step, params, moms, data_shape = build_step(model_name, batch)
    key = jax.random.PRNGKey(0)
    data = jax.random.normal(key, data_shape, jnp.float32)
    label = jax.random.randint(key, (batch,), 0, 10, jnp.int32)

    def body(_, st):
        return step(st[0], st[1], data, label)

    @jax.jit
    def run(p, m, k):
        return jax.lax.fori_loop(0, k, body, (p, m))

    k1, k2 = iters, iters * 5
    p, m = run(params, moms, k1)                    # compile + warm
    float(jnp.sum(p[next(iter(p))]))
    t0 = time.perf_counter()
    p, m = run(p, m, k1)
    float(jnp.sum(p[next(iter(p))]))
    t1 = time.perf_counter()
    p, m = run(p, m, k2)
    float(jnp.sum(p[next(iter(p))]))
    t2 = time.perf_counter()
    per_iter = ((t2 - t1) - (t1 - t0)) / (k2 - k1)
    return {"model": model_name, "batch": batch,
            "step_ms": round(per_iter * 1e3, 3),
            "images_per_sec": round(batch / per_iter, 0)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="MNIST_r05.json")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args()

    import jax
    print("backend:", jax.default_backend(), jax.devices())

    baselines = {"mlp": 103000.0, "lenet": 22500.0}  # 1x GTX 980, BASELINE.md
    records = []
    for name in ("mlp", "lenet"):
        rec = bench_model(name, args.batch, iters=args.iters)
        rec["baseline_gtx980_img_s"] = baselines[name]
        rec["vs_baseline"] = round(rec["images_per_sec"] / baselines[name], 2)
        print(json.dumps(rec))
        records.append(rec)

    out = {"device": str(jax.devices()[0]),
           "timing": "in-device fori_loop, chained params, slope-timed",
           "records": records}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
