"""Wall time of ``model.precompile``: parameter initialisation, lowering and
the XLA compile or the persistent-cache read of the train program.
"""

METRIC = {
    "name": "precompile_s",
    "unit": "s",
    "better": "lower",
    "source": "host_clock",
    "layer": "step builder + compile management (_build_train_step, utils/compile.py)",
    "moves": "setup_s",
}


def read(run):
    return run["precompile_s"]
