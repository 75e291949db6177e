"""The ``inception_bn`` configuration: the builder of the model's symbol
(``symbol``) and, independent of it and of the executor, the plain
reference (``logits``).

Ioffe & Szegedy, "Batch Normalization", arXiv:1502.03167, Fig. 5, with the
widths of MXNet's ``example/imagenet/inception-bn.py``, which are the
figure's: every convolution has a bias and is followed by BatchNorm
(eps 1e-3) and ReLU. An inception unit (MXNet's ``InceptionFactoryA``)
concatenates four branches: 1x1; 1x1 -> 3x3; 1x1 -> 3x3 -> 3x3; 3x3/1 pool
-> 1x1 projection. A downsample unit (``InceptionFactoryB``, 3c and 4e)
concatenates three: 1x1 -> 3x3/2; 1x1 -> 3x3 -> 3x3/2; 3x3/2 max pool.
Feature maps are the figure's 112, 56, 28, 14, 7: the two stem pools are
padded by 1 so that 112 -> 56 -> 28, as MXNet 0.5's pooling (which rounds
up) gives them. Block outputs: 256, 320, 576, 576, 576, 608, 608, 1056,
1024, 1024 channels (the figure's table prints 576 for 4c and 4d and 1024
for 4e, but its own columns sum to 608 and 1056, and MXNet's file builds
those). Average pooling divides by the window size, padding included, as
MXNet's does. 2.03 G multiply-adds an image.

The program's own ``mxnet_tpu.models.inception_bn`` is NOT this network
(no 1x1 branches, two-branch downsample units, three narrower reduce
layers: 1.57 G multiply-adds), so the benchmark builds the published one
here from the program's operators (PERF.md section 7 lists the repair).

Weights are named as MXNet names them: OIHW kernels
``conv_<n>_{weight,bias}``, ``bn_<n>_{gamma,beta}`` with
``bn_<n>_moving_{mean,var}``, ``fc1_{weight,bias}``. Images are NHWC.
"""

BN_EPS = 1e-3

# A: (name, 1x1, 3x3 reduce, 3x3, double-3x3 reduce, double-3x3, pool,
#     projection); B: (name, 3x3 reduce, 3x3, double-3x3 reduce, double-3x3)
UNITS = (
    ("3a", 64, 64, 64, 64, 96, "avg", 32),
    ("3b", 64, 64, 96, 64, 96, "avg", 64),
    ("3c", 128, 160, 64, 96),
    ("4a", 224, 64, 96, 96, 128, "avg", 128),
    ("4b", 192, 96, 128, 96, 128, "avg", 128),
    ("4c", 160, 128, 160, 128, 160, "avg", 128),
    ("4d", 96, 128, 192, 160, 192, "avg", 128),
    ("4e", 128, 192, 192, 256),
    ("5a", 352, 192, 320, 160, 224, "avg", 128),
    ("5b", 352, 192, 320, 192, 224, "max", 128),
)


# -- the builder: the program's operators, this file's architecture ---------

def symbol(num_classes=1000, layout="NHWC"):
    from mxnet_tpu import symbol as sym

    ch = 3 if layout == "NHWC" else 1

    def conv(data, num_filter, kernel, name, stride=(1, 1), pad=(0, 0)):
        c = sym.Convolution(data=data, name=f"conv_{name}", kernel=kernel,
                            stride=stride, pad=pad, num_filter=num_filter,
                            layout=layout)
        bn = sym.BatchNorm(data=c, name=f"bn_{name}", axis=ch)
        return sym.Activation(data=bn, name=f"relu_{name}", act_type="relu")

    def pool(data, kind, stride, name):
        return sym.Pooling(data=data, name=name, kernel=(3, 3),
                           stride=(stride, stride), pad=(1, 1),
                           pool_type=kind, layout=layout)

    def unit_a(x, name, n1, n3r, n3, nd3r, nd3, kind, proj):
        c1 = conv(x, n1, (1, 1), f"{name}_1x1")
        c3 = conv(x, n3r, (1, 1), f"{name}_3x3_reduce")
        c3 = conv(c3, n3, (3, 3), f"{name}_3x3", pad=(1, 1))
        d3 = conv(x, nd3r, (1, 1), f"{name}_double_3x3_reduce")
        d3 = conv(d3, nd3, (3, 3), f"{name}_double_3x3_0", pad=(1, 1))
        d3 = conv(d3, nd3, (3, 3), f"{name}_double_3x3_1", pad=(1, 1))
        p = conv(pool(x, kind, 1, f"{kind}_pool_{name}_pool"), proj, (1, 1),
                 f"{name}_proj")
        return sym.Concat(c1, c3, d3, p, name=f"ch_concat_{name}_chconcat",
                          dim=ch)

    def unit_b(x, name, n3r, n3, nd3r, nd3):
        c3 = conv(x, n3r, (1, 1), f"{name}_3x3_reduce")
        c3 = conv(c3, n3, (3, 3), f"{name}_3x3", stride=(2, 2), pad=(1, 1))
        d3 = conv(x, nd3r, (1, 1), f"{name}_double_3x3_reduce")
        d3 = conv(d3, nd3, (3, 3), f"{name}_double_3x3_0", pad=(1, 1))
        d3 = conv(d3, nd3, (3, 3), f"{name}_double_3x3_1", stride=(2, 2),
                  pad=(1, 1))
        return sym.Concat(c3, d3, pool(x, "max", 2, f"max_pool_{name}_pool"),
                          name=f"ch_concat_{name}_chconcat", dim=ch)

    x = sym.Variable("data")
    x = conv(x, 64, (7, 7), "conv1", stride=(2, 2), pad=(3, 3))
    x = pool(x, "max", 2, "pool1")
    x = conv(x, 64, (1, 1), "conv2red")
    x = conv(x, 192, (3, 3), "conv2", pad=(1, 1))
    x = pool(x, "max", 2, "pool2")
    for unit in UNITS:
        x = (unit_a if len(unit) == 8 else unit_b)(x, *unit)
    x = sym.Pooling(data=x, name="global_pool", kernel=(7, 7),
                    pool_type="avg", global_pool=True, layout=layout)
    x = sym.FullyConnected(data=sym.Flatten(data=x, name="flatten"),
                           name="fc1", num_hidden=num_classes)
    return sym.SoftmaxOutput(data=x, name="softmax")


# -- the plain reference: float32 jax.numpy / lax, inference BatchNorm ------

def logits(params, aux, images):
    import jax.numpy as jnp
    from jax import lax

    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    a = {k: jnp.asarray(v, jnp.float32) for k, v in aux.items()}

    def pool(x, kind, stride):
        dims, strides = (1, 3, 3, 1), (1, stride, stride, 1)
        pad = [(0, 0), (1, 1), (1, 1), (0, 0)]
        if kind == "max":
            return lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pad)
        return lax.reduce_window(x, 0.0, lax.add, dims, strides, pad) / 9.0

    def conv(x, name, stride=1, pad=0):
        y = lax.conv_general_dilated(
            x, p[f"conv_{name}_weight"], (stride, stride),
            [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "OIHW", "NHWC"),
            precision=lax.Precision.HIGHEST) + p[f"conv_{name}_bias"]
        y = (y - a[f"bn_{name}_moving_mean"]) \
            / jnp.sqrt(a[f"bn_{name}_moving_var"] + BN_EPS)
        return jnp.maximum(
            y * p[f"bn_{name}_gamma"] + p[f"bn_{name}_beta"], 0)

    def double(x, name, stride):
        d3 = conv(x, f"{name}_double_3x3_reduce")
        d3 = conv(d3, f"{name}_double_3x3_0", pad=1)
        return conv(d3, f"{name}_double_3x3_1", stride=stride, pad=1)

    x = conv(jnp.asarray(images, jnp.float32), "conv1", stride=2, pad=3)
    x = pool(x, "max", 2)
    x = conv(conv(x, "conv2red"), "conv2", pad=1)
    x = pool(x, "max", 2)
    for unit in UNITS:
        name, stride = unit[0], 1 if len(unit) == 8 else 2
        c3 = conv(conv(x, f"{name}_3x3_reduce"), f"{name}_3x3",
                  stride=stride, pad=1)
        if stride == 1:
            branches = [conv(x, f"{name}_1x1"), c3, double(x, name, 1),
                        conv(pool(x, unit[6], 1), f"{name}_proj")]
        else:
            branches = [c3, double(x, name, 2), pool(x, "max", 2)]
        x = jnp.concatenate(branches, axis=-1)
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(x, p["fc1_weight"].T,
                   precision=lax.Precision.HIGHEST) + p["fc1_bias"]
