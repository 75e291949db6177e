"""Weight initializers (reference: python/mxnet/initializer.py).

The dispatch-by-name-suffix contract is preserved: ``init(name, arr)`` fills
``arr`` in place according to what the parameter is (weight/bias/gamma/beta/
moving stats). Sampling uses the framework PRNG (mxnet_tpu.random), so
``mx.random.seed`` makes initialization reproducible.
"""

from __future__ import annotations

import math
import re

import numpy as np

from . import random as _random
from .base import MXNetError
from .ndarray import NDArray

__all__ = ["Initializer", "Uniform", "Normal", "Xavier", "One", "Zero", "Constant",
           "Mixed"]


class Initializer:
    """Base: routes parameters by name suffix, like the reference."""

    def __call__(self, name: str, arr: NDArray):
        if not isinstance(name, str):
            raise TypeError("name must be str")
        if name.endswith("upsampling"):
            self._init_bilinear(name, arr)
        elif name.endswith("bias"):
            self._init_bias(name, arr)
        elif name.endswith("gamma"):
            self._init_gamma(name, arr)
        elif name.endswith("beta"):
            self._init_beta(name, arr)
        elif name.endswith("weight"):
            self._init_weight(name, arr)
        elif name.endswith("moving_mean"):
            self._init_zero(name, arr)
        elif name.endswith("moving_var"):
            self._init_one(name, arr)
        elif name.endswith("moving_avg"):
            self._init_zero(name, arr)
        elif name.endswith(("expert_load", "mask_count")):
            # counts kept as auxiliary states: MixtureOfExperts' picks,
            # MaskedDiffusionOutput's rows
            self._init_zero(name, arr)
        else:
            self._init_default(name, arr)

    def _init_zero(self, _name, arr):
        arr[:] = 0.0

    def _init_one(self, _name, arr):
        arr[:] = 1.0

    def _init_bias(self, _name, arr):
        arr[:] = 0.0

    def _init_gamma(self, _name, arr):
        arr[:] = 1.0

    def _init_beta(self, _name, arr):
        arr[:] = 0.0

    def _init_bilinear(self, _name, arr):
        # bilinear upsampling kernel (reference keeps this for Deconvolution)
        shape = arr.shape
        weight = np.zeros(shape, dtype=np.float32)
        f = math.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(int(np.prod(shape))):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight.flat[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        arr[:] = weight

    def _init_weight(self, name, arr):
        raise NotImplementedError("virtual _init_weight")

    def _init_default(self, name, arr):
        raise MXNetError(
            f"Unknown parameter kind for {name!r}; initializer only handles "
            "names ending in weight/bias/gamma/beta/moving_{mean,var,avg}"
        )


class Uniform(Initializer):
    """U(-scale, scale) weights (reference: initializer.py Uniform)."""

    def __init__(self, scale=0.07):
        self.scale = scale

    def _init_weight(self, _name, arr):
        _random.uniform(-self.scale, self.scale, out=arr)


class Normal(Initializer):
    """N(0, sigma²) weights (reference: initializer.py Normal)."""

    def __init__(self, sigma=0.01):
        self.sigma = sigma

    def _init_weight(self, _name, arr):
        _random.normal(0.0, self.sigma, out=arr)


class Xavier(Initializer):
    """Glorot initialization (reference: initializer.py Xavier), with the
    rnd_type/factor_type/magnitude extensions later MXNet added."""

    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = float(magnitude)

    def _init_weight(self, _name, arr):
        shape = arr.shape
        if len(shape) == 3:
            # a stack of (out, in) matrices (MixtureOfExperts' experts):
            # each expert's own fan, whatever the number stacked
            fan_out, fan_in = shape[1], shape[2]
        else:
            fan_out = shape[0]
            fan_in = int(np.prod(shape[1:])) if len(shape) > 1 else shape[0]
        if self.factor_type == "avg":
            factor = (fan_in + fan_out) / 2.0
        elif self.factor_type == "in":
            factor = fan_in
        elif self.factor_type == "out":
            factor = fan_out
        else:
            raise MXNetError(f"bad factor_type {self.factor_type}")
        scale = math.sqrt(self.magnitude / factor)
        if self.rnd_type == "uniform":
            _random.uniform(-scale, scale, out=arr)
        elif self.rnd_type == "gaussian":
            _random.normal(0.0, scale, out=arr)
        else:
            raise MXNetError(f"bad rnd_type {self.rnd_type}")


class One(Initializer):
    def _init_weight(self, _name, arr):
        arr[:] = 1.0

    def _init_default(self, _name, arr):
        arr[:] = 1.0


class Zero(Initializer):
    def _init_weight(self, _name, arr):
        arr[:] = 0.0

    def _init_default(self, _name, arr):
        arr[:] = 0.0


class Constant(Initializer):
    def __init__(self, value):
        self.value = value

    def _init_weight(self, _name, arr):
        arr[:] = self.value

    def _init_default(self, _name, arr):
        arr[:] = self.value


class _Fill(Constant):
    """``Mixed``'s bare number: the value, whatever the name's suffix."""

    def __call__(self, _name, arr):
        arr[:] = self.value


class Mixed(Initializer):
    """Initializers by name (reference: initializer.py ``Mixed``): the
    first of ``patterns`` (regular expressions, searched in the name) that
    matches gives a parameter its initializer; end with ``".*"``, a name
    that matches none is an error. An initializer may be given as what a
    configuration file can hold: a dict ``{"name": "Xavier", ...}`` of a
    class of this module and its arguments, or a bare number, which fills
    the matching arrays with itself whatever their suffix (a norm's
    ``gamma`` that starts elsewhere than at one)."""

    def __init__(self, patterns, initializers):
        if len(patterns) != len(initializers):
            raise MXNetError(f"Mixed: {len(patterns)} patterns for "
                             f"{len(initializers)} initializers")
        self.map = [(re.compile(p), self._made(i))
                    for p, i in zip(patterns, initializers)]

    @staticmethod
    def _made(spec):
        if isinstance(spec, dict):
            spec = dict(spec)
            return globals()[spec.pop("name")](**spec)
        if isinstance(spec, (int, float)):
            return _Fill(spec)
        return spec

    def __call__(self, name, arr):
        for pattern, init in self.map:
            if pattern.search(name):
                return init(name, arr)
        raise MXNetError(f"Mixed: no pattern matches {name!r}; end the "
                         "patterns with \".*\"")
