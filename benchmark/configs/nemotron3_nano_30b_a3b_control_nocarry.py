"""Control of ``nemotron3_nano_30b_a3b``'s ``reference_tolerance``: the
plain reference beside this file with the state of every Mamba-2 layer NOT
carried across the boundaries of the program's chunks: the per-position
recurrence starts from zero at every ``chunk_size``-th position, as a
chunked scan that dropped its carry would compute. It shows that the
comparison sees the state the chunks hand on. It must read `correct`
false. The runner is pointed at it by ``"reference":
"nemotron3_nano_30b_a3b_control_nocarry.py"`` in a copy of the
configuration's file."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "nemotron3_nano_30b_a3b_plain_nocarry", os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "nemotron3_nano_30b_a3b.py"))
plain = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(plain)
recurrence = plain.selective_scan


def logits(params, aux, ids, config=None):
    chunk = (config or plain.file_config())["chunk_size"]

    def restarted(x, dt, a, b, c):
        """Every chunk of ``chunk`` positions a sequence of its own."""
        batch, t = x.shape[:2]

        def cut(v):
            return v.reshape((batch * t // chunk, chunk) + v.shape[2:])

        return recurrence(cut(x), cut(dt), a, cut(b), cut(c)).reshape(
            x.shape)

    plain.selective_scan = restarted
    try:
        return plain.logits(params, aux, ids, config)
    finally:
        plain.selective_scan = recurrence
