"""The chunked scans of the Mamba-2 layers of the cell's own configuration
(``run["config"]``: its ``hybrid_override_pattern`` cut to its depth,
heads, head width, groups, state, chunk, positions) against their
roofline: ``max(FLOP / peak, bytes / bandwidth)`` of forward and backward
once each (``ssm_costs.py``) over the time the instructions under the
mixer's ``ssd`` scope took in a step, the recomputed forward in the time
alone. Nothing where the configuration has no Mamba-2 layer or the
program no such scope.
"""

import os
import runpy

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = runpy.run_path(os.path.join(HERE, "scopes.py"))
KERNELS = runpy.run_path(os.path.join(HERE, "kernel_costs.py"))
COSTS = runpy.run_path(os.path.join(HERE, "ssm_costs.py"))

METRIC = {
    "name": "ssm_scan_roofline_pct",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    cfg = run["config"]
    pattern = cfg.get("hybrid_override_pattern", "")[
        :cfg.get("num_hidden_layers", 0)]
    if "M" not in pattern or not run.get("peak"):
        return None
    took = SCOPES["ms_per_step"](run, COSTS["SCAN"])
    if not took:
        return None
    least = sum(KERNELS["roofline_seconds"](cost, run["peak"])
                for cost in COSTS["ssd"](
                    cfg["input_shape"][0], cfg["mamba_num_heads"],
                    cfg["mamba_head_dim"], cfg["n_groups"],
                    cfg["ssm_state_size"], cfg["chunk_size"]))
    return 100.0 * 1e3 * least * pattern.count("M") \
        * run["per_chip_batch"] / took
