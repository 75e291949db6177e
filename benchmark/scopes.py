"""Device time by the program's scope names.

The profiler's device events carry the HLO instruction's name and nothing
else (``trace_reduce``: ``fusion.21``; a Pallas kernel's custom call has
the kernel's ``name``, ``flash_fwd.1``). The scope a ``jax.named_scope``
gave the code that made the instruction is in the compiled program's
text, as the ``op_name`` of the instruction's ``metadata``. ``hlo_scopes`` reads that text into a map
instruction -> scope, and ``ms_per_step`` joins it with the trace's
``program_op_seconds`` (the instructions that ran inside the train
program's executions: a name is unique in its program only): a per-layer
metric file can read one kernel's, one layer's or one phase's device time.

What an ``op_name`` looks like (the train program, read on the CPU here
and on the chip, PR 26)::

    jit(step)/jvp(stage1_unit1_br1_conv/Convolution)/conv_general_dilated
    jit(step)/transpose(jvp(stage1_unit1_br1_conv/Convolution))/conv_gen...
    jit(step)/optimizer/update/mul
    jit(step)/jvp()/convert_element_type      the parameters' cast
    jit(step)/metric/update/reduce_sum

The executor emits every operator under ``<node>/<Operator>``; what
``jax.value_and_grad`` traces is wrapped in ``jvp(...)``, its transpose
(the backward pass) in ``transpose(jvp(...))``. An instruction has ONE
scope here: its own ``op_name``, else that of the root of the computation
it calls, else (that root being a bitcast or a tuple, which have none) of
the nearest instruction above the root that has one, else ``None``. XLA
fuses across scopes and gives a fusion the ``op_name`` of the product in
it, not of its root: on the chip the optimizer's update rides as the
epilogue of the weight-gradient convolutions and counts as backward there
(PERF.md section 6, PR 26), so the bucket of the update's own instructions
is named ``optimizer_unfused``.
"""

from __future__ import annotations

import re

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_ATTRIBUTES = re.compile(r"\), [a-z_]+=")

BUCKETS = ("forward", "backward", "optimizer_unfused", "unscoped")


def hlo_scopes(text):
    """``{instruction: op_name or None}`` for every instruction of every
    computation of an optimized-HLO module's text."""
    own, calls, operands, roots = {}, {}, {}, {}
    computation = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        if line.startswith("}"):
            computation = None
            continue
        m = _INSTRUCTION.match(line) if computation else None
        if not m:
            continue
        is_root, name, rest = m.groups()
        scope = _OP_NAME.search(rest)
        own[name] = scope.group(1) if scope else None
        called = _CALLS.search(rest)
        if called:
            calls[name] = called.group(1)
        # "f32[8]{0} add(%a, %b), metadata=...": what follows the opcode
        operands[name] = _OPERAND.findall(
            _ATTRIBUTES.split(rest.split("(", 1)[-1], 1)[0])
        if is_root:
            roots[computation] = name

    def inside(name, depth):
        """Within a called computation: the instruction's own scope, else
        the nearest one above it (a root that is a bitcast or a tuple has
        none of its own)."""
        if own.get(name) is not None or depth > 16:
            return own.get(name)
        if name in calls:
            found = called_root(name, depth + 1)
            if found is not None:
                return found
        for operand in operands.get(name, ()):
            found = inside(operand, depth + 1)
            if found is not None:
                return found
        return None

    def called_root(name, depth=0):
        root = roots.get(calls.get(name))
        return inside(root, depth) if root is not None else None

    return {name: scope if scope is not None else called_root(name)
            for name, scope in own.items()}


def bucket(scope):
    """Which of ``BUCKETS`` an instruction of the train program is in."""
    if not scope:
        return "unscoped"
    if "transpose(jvp(" in scope:
        return "backward"
    if "jvp(" in scope:
        return "forward"
    if "optimizer/update" in scope:
        return "optimizer_unfused"
    return "unscoped"


def _ms_per_step(run, keep, other_programs=False):
    """Milliseconds a step of the train program's instructions that ran
    and whose scope ``keep`` accepts, with ``other_programs`` also of
    whatever else ran in the span; ``None`` where the run has no trace or
    no HLO text, or where nothing that ran is kept (nothing to read is
    nothing, never 0)."""
    trace, scopes = run.get("trace"), run.get("hlo_scopes")
    if not trace or not scopes or not trace.get("program_op_seconds"):
        return None
    own = trace["program_op_seconds"]
    hit = [t for name, t in own.items() if keep(scopes.get(name))]
    others = sum(trace["op_seconds"].values()) - sum(own.values())
    if other_programs and others > 0:
        hit.append(others)
    return 1e3 * sum(hit) / trace["span_steps"] if hit else None


def ms_per_step(run, pattern):
    """Device milliseconds a step of the train program's instructions whose
    scope matches the regular expression ``pattern`` (``re.search``), each
    instruction counted once: one kernel's, one layer's or one phase's
    time."""
    want = re.compile(pattern)
    return _ms_per_step(run, lambda scope: bool(scope and want.search(scope)))


def bucket_ms_per_step(run, which):
    """Device milliseconds a step of one of ``BUCKETS``. Every instruction
    that ran in the traced span is in exactly one, those of another
    program or without a scope in ``unscoped``, so the four add up to the
    summed instruction time of a step."""
    return _ms_per_step(run, lambda scope: bucket(scope) == which,
                        other_programs=which == "unscoped")
