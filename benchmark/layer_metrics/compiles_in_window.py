"""XLA compiles plus jit cache misses between the window's start and end, from
``mx.utils.compile_stats()``. Must be 0: a run with any is ``correct: false``.
"""

METRIC = {
    "name": "compiles_in_window",
    "unit": "count",
    "better": "lower",
    "source": "program_counter",
    "layer": "step builder + compile management (_build_train_step, utils/compile.py)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return run["compiles_in_window"]
