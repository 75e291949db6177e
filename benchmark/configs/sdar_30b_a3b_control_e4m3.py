"""Control of ``sdar_30b_a3b``'s ``reference_tolerance``: the plain
reference beside this file with its WEIGHTS rounded to ``float8_e4m3fn``,
the nearest precision below the cell's bfloat16. It must read `correct`
false. The runner is pointed at it by ``"reference":
"sdar_30b_a3b_control_e4m3.py"`` in a copy of the configuration's file."""

import importlib.util
import os

import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "sdar_30b_a3b_plain", os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "sdar_30b_a3b.py"))
plain = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(plain)


def logits(params, aux, ids, config=None):
    rounded = {k: jnp.asarray(v, jnp.float32).astype(jnp.float8_e4m3fn)
               .astype(jnp.float32) for k, v in params.items()}
    return plain.logits(rounded, aux, ids, config)
