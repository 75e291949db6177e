"""Control of ``nemotron3_nano_30b_a3b``'s ``reference_tolerance``: the
plain reference beside this file computed in ``float8_e4m3fn``, the nearest
precision below the cell's bfloat16. Every matrix product's operands (each
``dot_general`` of the reference's program: the projections, the scan's
``S_t C_t`` at every position, the attention's scores and its weighted sum
of values, the experts, the head) are rounded to e4m3 and the product
accumulated in float32, unscaled, by ``in_e4m3`` of
``kanana2_30b_a3b_control_e4m3.py`` beside this file (which follows the
products into the bodies of ``lax.scan`` / ``lax.map`` and nested
``jit``). It must read `correct` false. The runner is pointed at it by
``"reference": "nemotron3_nano_30b_a3b_control_e4m3.py"`` in a copy of the
configuration's file."""

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "nemotron3_nano_30b_a3b_" + name.replace(".", "_"),
        os.path.join(HERE, name))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


plain = _load("nemotron3_nano_30b_a3b.py")
in_e4m3 = _load("kanana2_30b_a3b_control_e4m3.py").in_e4m3


def logits(params, aux, ids, config=None):
    return in_e4m3(lambda p, a, i: plain.logits(p, a, i, config))(
        params, aux, ids)
