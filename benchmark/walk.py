"""The layer walk: the FLOP recipe in a configuration's file is data, the
model is code, and this derives the one from the other so that a test can
require them equal.

The built symbol's graph (``Symbol.tojson()``: every node with its
operator, its inputs and its parameters) is walked node by node, with the
shapes ``infer_shape`` gives at batch 1. What a node contributes to
``flops_per_sample.layers`` is decided by its OPERATOR's walker,
``walkers/<Operator>.py``::

    layers(node, in_shapes, out_shapes) -> [entries of flops.py]

``node`` is the graph's entry (``op``, ``name``, ``param``) with ``args``
added: the operator's argument names, in the order of ``in_shapes``. An
operator that takes a learnable argument (a variable that is neither the
data nor a label) MUST have a walker, even one that returns nothing: a
product that no file accounts for is an error that names the operator. An
operator without learnable arguments and without a walker contributes
nothing. A later PR brings ``walkers/<ItsOperator>.py`` beside its
configuration and edits no file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class WalkError(Exception):
    """An operator with a learnable argument has no walker."""


def _walker(path, op):
    spec = importlib.util.spec_from_file_location("bench_walker_" + op, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def is_input(name):
    """Data and labels, as ``run.py`` tells them from parameters."""
    return name == "data" or name.endswith("label")


def sample_shape(config):
    """One sample's shape, no batch axis: ``input_shape``, or ``image``."""
    return tuple(config["input_shape"] if "input_shape" in config
                 else config["image"])


def layers_of(symbol, config, here=HERE):
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.ops.registry import OPS

    internals = symbol.get_internals()
    inputs = [n for n in symbol.list_arguments() if is_input(n)]
    shape = (1, *sample_shape(config))
    try:
        arg_shapes, out_shapes, _ = internals.infer_shape(**{inputs[0]: shape})
    except MXNetError:
        # a label that passes through an operator of its own is not
        # inferred from the head: it has the data's shape (next-token ids)
        arg_shapes, out_shapes, _ = internals.infer_shape(
            **{n: shape for n in inputs})
    shapes = dict(zip(internals.list_arguments(), arg_shapes))
    shapes.update(zip(internals.list_outputs(), out_shapes))
    nodes = json.loads(symbol.tojson())["nodes"]
    outputs = []          # per node, the names of its outputs
    walked = []
    for node in nodes:
        if node["op"] == "null":
            outputs.append([node["name"]])
            continue
        op = OPS.create(node["op"], **node.get("param", {}))
        outs = op.list_outputs()
        outputs.append([f"{node['name']}_output"] if len(outs) == 1 else
                       [f"{node['name']}_{o}" for o in outs])
        sources = [nodes[i] for i, _ in node["inputs"]]
        learnable = [s["name"] for s in sources
                     if s["op"] == "null" and not is_input(s["name"])]
        path = os.path.join(here, "walkers", node["op"] + ".py")
        if not os.path.isfile(path):
            if learnable:
                raise WalkError(
                    f"operator {node['op']!r} (node {node['name']!r}) takes "
                    f"the learnable {learnable} and has no walker: add "
                    f"benchmark/walkers/{node['op']}.py")
            continue
        walked += _walker(path, node["op"]).layers(
            dict(node, args=op.list_arguments()),
            [shapes[outputs[i][k]] for i, k in node["inputs"]],
            [shapes[name] for name in outputs[-1]])
    return walked
