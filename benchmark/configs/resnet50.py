"""Plain reference for the ``resnet50`` configuration: the forward pass to
the logits in straightforward float32 ``jax.numpy``/``lax``, inference-mode
BatchNorm (moving statistics), no fusion passes, no executor.

He et al., "Deep Residual Learning for Image Recognition",
arXiv:1512.03385, Table 1, 50-layer column: 7x7/2 stem, 3x3/2 max pool,
bottleneck stages (3, 4, 6, 3) of widths (256, 512, 1024, 2048), global
average pool, 1000-way fully-connected layer. Departure from the paper,
shared with the system under test: the stride of a stage's first unit sits
on its 3x3 convolution ("v1.5"), not on its first 1x1.

Weights are the system's own: OIHW convolution kernels named
``<layer>_conv_weight``, BatchNorm ``<layer>_bn_{gamma,beta}`` with
``<layer>_bn_moving_{mean,var}``, ``fc1_{weight,bias}``. Images are NHWC.
"""

import jax.numpy as jnp
from jax import lax

UNITS = (3, 4, 6, 3)
BN_EPS = 1e-5


def _conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OIHW", "NHWC"),
        precision=lax.Precision.HIGHEST)


def _conv_bn(p, a, x, name, stride=1, pad=0, relu=True):
    y = _conv(x, p[f"{name}_conv_weight"], stride, pad)
    y = (y - a[f"{name}_bn_moving_mean"]) \
        / jnp.sqrt(a[f"{name}_bn_moving_var"] + BN_EPS)
    y = y * p[f"{name}_bn_gamma"] + p[f"{name}_bn_beta"]
    return jnp.maximum(y, 0) if relu else y


def _bottleneck(p, a, x, name, stride, project):
    y = _conv_bn(p, a, x, f"{name}_br1")
    y = _conv_bn(p, a, y, f"{name}_br2", stride=stride, pad=1)
    y = _conv_bn(p, a, y, f"{name}_br3", relu=False)
    if project:
        x = _conv_bn(p, a, x, f"{name}_sc", stride=stride, relu=False)
    return jnp.maximum(y + x, 0)


def logits(params, aux, images):
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    a = {k: jnp.asarray(v, jnp.float32) for k, v in aux.items()}
    x = _conv_bn(p, a, jnp.asarray(images, jnp.float32), "stem",
                 stride=2, pad=3)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for i, n_unit in enumerate(UNITS):
        for j in range(n_unit):
            x = _bottleneck(p, a, x, f"stage{i + 1}_unit{j + 1}",
                            stride=2 if (i > 0 and j == 0) else 1,
                            project=j == 0)
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(x, p["fc1_weight"].T,
                   precision=lax.Precision.HIGHEST) + p["fc1_bias"]
