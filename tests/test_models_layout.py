"""Layout portability of the conv model zoo: NCHW (reference parity) and
NHWC (TPU fast path) must compute the same function from the same OIHW
weights — the contract models/resnet.py established, now also carried by
models/inception.py (the BASELINE anchor architecture)."""

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.executor import _build_graph_fn
from mxnet_tpu.models.inception import inception_bn_cifar


def _init(sym, input_shapes, seed=0):
    arg_shapes, _, aux_shapes = sym.infer_shape(**input_shapes)
    rng = np.random.RandomState(seed)
    params = {}
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name in input_shapes:
            continue
        if name.endswith("gamma"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith(("beta", "bias")):
            params[name] = jnp.zeros(shape, jnp.float32)
        else:
            params[name] = jnp.asarray(
                (rng.randn(*shape) * 0.05).astype(np.float32))
    aux = {name: (jnp.ones(s, jnp.float32) if name.endswith("var")
                  else jnp.zeros(s, jnp.float32))
           for name, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    return params, aux


def test_inception_bn_nhwc_matches_nchw():
    rng = np.random.RandomState(1)
    x = rng.randn(2, 3, 32, 32).astype(np.float32)
    label = np.zeros((2,), np.float32)

    outs = {}
    for layout in ("NCHW", "NHWC"):
        sym = inception_bn_cifar(num_classes=10, layout=layout)
        data = x if layout == "NCHW" else np.transpose(x, (0, 2, 3, 1))
        shapes = {"data": data.shape, "softmax_label": (2,)}
        params, aux = _init(sym, shapes)  # same seed -> identical OIHW
        graph_fn = _build_graph_fn(sym, is_train=False)
        zero_key = jnp.zeros((2,), jnp.uint32)
        res, _ = jax.jit(lambda p, a, d: graph_fn(  # mxlint: disable=MX303
            {**p, "data": d, "softmax_label": jnp.asarray(label)}, a,
            zero_key))(params, aux, jnp.asarray(data))
        outs[layout] = np.asarray(res[0])

    np.testing.assert_allclose(outs["NHWC"], outs["NCHW"],
                               atol=2e-5, rtol=1e-4)


def test_inception_bn_imagenet_infer_shape_both_layouts():
    from mxnet_tpu.models.inception import inception_bn

    per_layout = {}
    for layout, shape in (("NCHW", (2, 3, 224, 224)),
                          ("NHWC", (2, 224, 224, 3))):
        sym = inception_bn(num_classes=1000, layout=layout)
        arg_shapes, out_shapes, _ = sym.infer_shape(
            data=shape, softmax_label=(2,))
        assert out_shapes[0] == (2, 1000)
        per_layout[layout] = dict(zip(sym.list_arguments(), arg_shapes))
    # every weight shape identical across layouts (checkpoint portability:
    # conv weights stay OIHW, the head sees the same channel count)
    for name, shp in per_layout["NCHW"].items():
        if name == "data":
            continue
        assert per_layout["NHWC"][name] == shp, (name, shp)
