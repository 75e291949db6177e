"""The flash kernels of the block-diffusion attention layers of the cell's
own configuration (``run["config"]``: heads, head size, positions, block
length, depth) against their roofline: ``max(FLOP / peak, bytes /
bandwidth)`` of forward and backward once each (``blockdiff_costs.py``: the
products the mask shows only) over the time the kernels took in a step
under the operator's scope, the recomputed forward in the time alone.
Nothing where the configuration has no block length or the program no such
kernels.
"""

import os
import runpy

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DECODER = runpy.run_path(os.path.join(HERE, "decoder_metrics.py"))
COSTS = runpy.run_path(os.path.join(HERE, "blockdiff_costs.py"))

METRIC = {
    "name": "attention_blockdiff_roofline_pct",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    cfg = run["config"]
    if "block_length" not in cfg or not run.get("peak"):
        return None
    took = DECODER["SCOPES"]["ms_per_step"](
        run, COSTS["SCOPE"] + DECODER["FLASH"])
    if not took:
        return None
    least = sum(
        DECODER["COSTS"]["roofline_seconds"](cost, run["peak"])
        for cost in COSTS["flash_attention"](
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["input_shape"][0] // 2, cfg["block_length"],
            cfg["head_dim"], cfg["head_dim"]))
    return 100.0 * 1e3 * least * cfg["num_hidden_layers"] \
        * run["per_chip_batch"] / took
