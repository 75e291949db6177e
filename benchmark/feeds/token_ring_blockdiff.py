"""Feeder ``token_ring_blockdiff``: ``token_ring`` for block-diffusion
training. A ring of distinct batches is made ON THE DEVICE from the seed
and handed to ``fit`` as device-backed NDArrays.

A batch is int32 ids ``(rows, 2 T)``, a sequence's noisy copy ``xt``
followed by its clean copy ``x0``, and int32 labels ``(rows, T)``, ``x0``
again: ``2 T`` is ``config["input_shape"][0]``, ``rows`` is
``per_chip_batch`` x chips.

``x0`` is ``token_ring``'s Markov chain (a seeded permutation gives every
id a successor, followed with probability ``follow_p``, else a uniform id)
over the ids ``0 .. mask_id - 1``, ``config["mask_id"]`` being the last
row of the chip's slice of the vocabulary (``vocab_rows - 1``). ``xt`` is
the absorbing schedule at its discrete steps: of every block of
``config["block_length"]`` positions exactly ``k`` are replaced by the
mask id, ``k`` uniform on ``1 .. block_length`` and the positions uniform,
both from the seed. The masked share of the rows is ``(B + 1) / (2 B)`` in
expectation.

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
        length = int(config["input_shape"][0]) // 2
        block, mask_id = int(config["block_length"]), int(config["mask_id"])
        if mask_id != int(config["vocab_rows"]) - 1 or length % block:
            raise catalog.BenchmarkError(
                f"token_ring_blockdiff: mask id {mask_id} is not the last "
                f"of {config['vocab_rows']} rows, or blocks of {block} do "
                f"not divide {length} positions")
        vocab = mask_id                      # the ids x0 is drawn from
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
            k_first, k_follow, k_other, k_count, k_where = \
                jax.random.split(key, 5)
            follow = jax.random.bernoulli(k_follow, follow_p,
                                          (length - 1, rows))
            other = jax.random.randint(k_other, (length - 1, rows), 0, vocab,
                                       jnp.int32)

            def step(token, drawn):
                nxt = jnp.where(drawn[0], successor[token], drawn[1])
                return nxt, nxt

            first = jax.random.randint(k_first, (rows,), 0, vocab, jnp.int32)
            _, rest = jax.lax.scan(step, first, (follow, other))
            x0 = jnp.concatenate([first[None], rest]).T.astype(jnp.int32)
            # of every block, the k positions that drew the smallest numbers
            blocks = (rows, length // block, block)
            count = jax.random.randint(k_count, blocks[:2] + (1,), 1,
                                       block + 1)
            drawn = jax.random.uniform(k_where, blocks)
            rank = jnp.argsort(jnp.argsort(drawn, axis=-1), axis=-1)
            masked = (rank < count).reshape(rows, length)
            xt = jnp.where(masked, jnp.int32(mask_id), x0)
            return jnp.concatenate([xt, x0], axis=1), x0

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
        """``n`` seeded rows of ids, noisy copy then clean copy (host
        copies), for the reference check."""
        x, _ = self.iter.ring[0]
        return np.asarray(x[:n])


make = Feed
