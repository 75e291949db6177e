"""Control of ``sdar_30b_a3b``'s ``reference_tolerance``: the plain
reference beside this file under a plain CAUSAL mask over the 2 T rows
(noisy copy, then clean copy) in place of the block-diffusion staircase.
It must read `correct` false. The runner is pointed at it by
``"reference": "sdar_30b_a3b_control_causal.py"`` in a copy of the
configuration's file."""

import importlib.util
import os

import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "sdar_30b_a3b_causal", os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "sdar_30b_a3b.py"))
plain = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(plain)    # a copy of its own: ``seen`` is replaced
plain.seen = lambda query_rows, t, block: \
    jnp.arange(2 * t)[None, :] <= query_rows[:, None]

logits = plain.logits
