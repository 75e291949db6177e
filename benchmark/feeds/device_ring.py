"""Feeder ``device_ring``: the input feed is bypassed.

A ring of distinct batches is made ON THE DEVICE from the seed, in one
jitted program called once per batch, and handed to ``fit`` by a DataIter
as device-backed NDArrays: ``fit``'s feed thread finds them already placed
and moves nothing. With several chips a batch is sharded over its rows on
a ``dp`` mesh of the cell's devices, the layout ``fit`` places batches in.

Data recipe (a copy of ``chip_smoke.synthetic_images``): standard-normal
pixels, two separable classes labelled 0/1 on the model's head, the class
shift added to every pixel, so the loss can fall within a few steps.

Traffic parameters: ``ring`` (distinct batches), ``steps_per_epoch``,
``warmup_steps`` (the length of the first, untimed epoch: enough to pass
the ring and reach the epoch tail, and no more, because every run of every
later check pays it in ``setup_s``; left out, a whole epoch), ``dtype`` (of
the images), ``class_shift``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

import mxnet_tpu as mx


class RingIter(mx.io.DataIter):
    def __init__(self, ring, steps_per_epoch, warmup_steps, data_name,
                 label_name):
        super().__init__()
        self.ring = ring
        self.steps = int(steps_per_epoch)
        self.limit = int(warmup_steps)     # of the epoch being handed out
        self.names = (data_name, label_name)
        self.batch_size = int(ring[0][0].shape[0])
        self.cursor = 0
        self.handed = 0

    def reset(self):
        """``fit`` resets at every epoch's start. Once an epoch has been
        handed out whole, the warm-up is over: every later epoch has
        ``steps_per_epoch`` steps."""
        if self.cursor >= self.limit:
            self.limit = self.steps
        self.cursor = 0

    def next(self):
        if self.cursor >= self.limit:
            raise StopIteration
        with jax.profiler.TraceAnnotation("bench.feed.next"):
            x, y = self.ring[self.handed % len(self.ring)]
            batch = mx.io.DataBatch([mx.nd.NDArray(x)], [mx.nd.NDArray(y)])
            self.cursor += 1
            self.handed += 1
        return batch

    @property
    def provide_data(self):
        return [(self.names[0], tuple(self.ring[0][0].shape))]

    @property
    def provide_label(self):
        return [(self.names[1], tuple(self.ring[0][1].shape))]


class Feed:
    def __init__(self, traffic, config, devices, seed, data_name,
                 label_name):
        rows = int(config["per_chip_batch"]) * len(devices)
        shape = (rows, *config["image"])
        dtype = jnp.dtype(traffic["dtype"])
        shift = float(traffic["class_shift"])
        if len(devices) == 1:
            sharding = SingleDeviceSharding(devices[0])
        else:
            sharding = NamedSharding(Mesh(np.array(devices), ("dp",)),
                                     PartitionSpec("dp"))

        @functools.partial(jax.jit, out_shardings=(sharding, sharding))
        def make_batch(key):
            y = (jnp.arange(rows) % 2).astype(jnp.float32)
            x = jax.random.normal(key, shape, jnp.float32) \
                + (y * 2 - 1)[:, None, None, None] * shift
            return x.astype(dtype), y

        keys = jax.random.split(jax.random.PRNGKey(seed), int(traffic["ring"]))
        ring = [make_batch(k) for k in keys]
        jax.block_until_ready(ring)
        self.iter = RingIter(
            ring, traffic["steps_per_epoch"],
            traffic.get("warmup_steps", traffic["steps_per_epoch"]),
            data_name, label_name)
        self.steps_per_epoch = self.iter.steps
        self.batch_rows = rows

    def check_rows(self, n):
        """``n`` seeded images (host copies) for the reference check."""
        x, _ = self.iter.ring[0]
        return np.asarray(x[:n].astype(jnp.float32))


make = Feed
