"""Feeder ``token_ring``: ``device_ring`` for a model whose sample is a
sequence of token ids. The input feed is bypassed in the same way: a ring
of distinct batches is made ON THE DEVICE from the seed, in one jitted
program called once per batch, and handed to ``fit`` as device-backed
NDArrays; with several chips a batch is sharded over its rows on a ``dp``
mesh of the cell's devices.

A batch is int32 ids ``(rows, T)`` and int32 next-token labels ``(rows,
T)``: ``T`` is ``config["input_shape"][0]``, ids lie in ``[0,
config["vocab_rows"])``, ``rows`` is ``per_chip_batch`` x chips.

Data recipe, a Markov chain: a permutation drawn from the seed gives every
id one successor. A sequence starts at a uniform id; each next token is
the successor of the one before with probability ``follow_p``, else a
uniform id. The label at a position is the token that follows it (the
last position's is drawn by the same rule). A model that learns the permutation
reaches a loss near ``-(p' ln p' + (1 - p') ln((1 - p') / (V - 1)))`` with
``p' = follow_p + (1 - follow_p) / V``, well under ``ln V``, so that the
runner's "loss did not fall" means something within a warm-up epoch.

Traffic parameters: ``ring``, ``steps_per_epoch``, ``warmup_steps`` (as in
``device_ring``), ``follow_p``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

import catalog

RingIter = catalog.load_feeder("device_ring").RingIter   # the file beside


class Feed:
    def __init__(self, traffic, config, devices, seed, data_name,
                 label_name):
        rows = int(config["per_chip_batch"]) * len(devices)
        length = int(config["input_shape"][0])
        vocab = int(config["vocab_rows"])
        follow_p = float(traffic["follow_p"])
        if len(devices) == 1:
            sharding = SingleDeviceSharding(devices[0])
        else:
            sharding = NamedSharding(Mesh(np.array(devices), ("dp",)),
                                     PartitionSpec("dp"))
        root = jax.random.PRNGKey(seed)
        successor = jax.random.permutation(jax.random.fold_in(root, 1), vocab)

        @functools.partial(jax.jit, out_shardings=(sharding, sharding))
        def make_batch(key):
            k_first, k_follow, k_other = jax.random.split(key, 3)
            follow = jax.random.bernoulli(k_follow, follow_p,
                                          (length, rows))
            other = jax.random.randint(k_other, (length, rows), 0, vocab,
                                       jnp.int32)

            def step(token, drawn):
                nxt = jnp.where(drawn[0], successor[token], drawn[1])
                return nxt, nxt

            first = jax.random.randint(k_first, (rows,), 0, vocab, jnp.int32)
            _, rest = jax.lax.scan(step, first, (follow, other))
            tokens = jnp.concatenate([first[None], rest]).T   # (rows, T + 1)
            return tokens[:, :-1].astype(jnp.int32), \
                tokens[:, 1:].astype(jnp.int32)

        keys = jax.random.split(jax.random.fold_in(root, 2),
                                int(traffic["ring"]))
        ring = [make_batch(k) for k in keys]
        jax.block_until_ready(ring)
        self.iter = RingIter(
            ring, traffic["steps_per_epoch"],
            traffic.get("warmup_steps", traffic["steps_per_epoch"]),
            data_name, label_name)
        self.steps_per_epoch = self.iter.steps
        self.batch_rows = rows

    def check_rows(self, n):
        """``n`` seeded id rows (host copies) for the reference check."""
        x, _ = self.iter.ring[0]
        return np.asarray(x[:n])


make = Feed
