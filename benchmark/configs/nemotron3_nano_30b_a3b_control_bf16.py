"""Witness for ``nemotron3_nano_30b_a3b``'s ``reference_tolerance``: the
plain reference beside this file with every product's operands rounded to
bfloat16 and accumulated in float32, which is what the cell's compute type
does to the program. Put in the reference's place it shows how far the
MODEL moves under the program's precision, whatever the program: a reading
near the cell's own says the cell's distance from the float32 reference is
the drawn model's sensitivity to rounding and not a fault of a kernel. On a
TPU only: there a float32 product at this precision is one bfloat16 pass;
on the CPU the setting changes nothing and this reads as the reference.
The runner is pointed at it by ``"reference":
"nemotron3_nano_30b_a3b_control_bf16.py"`` in a copy of the configuration's
file."""

import importlib.util
import os

import jax

_spec = importlib.util.spec_from_file_location(
    "nemotron3_nano_30b_a3b_plain", os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "nemotron3_nano_30b_a3b.py"))
plain = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(plain)


def logits(params, aux, ids, config=None):
    with jax.default_matmul_precision("bfloat16"):
        return plain.logits(params, aux, ids, config)
