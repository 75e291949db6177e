"""Picks that fell on the experts held here, a token and expert layer, from
the traced epochs' ``fit.epoch.expert_load`` records: 1.0 where routing is
uniform over all the router's experts (top_k x experts_held / num_experts).
Its place is 1.0 and neither end is better; ``lower`` because above 1.0
this rank computes more than its share of the routed rows.
"""

import os
import runpy

DECODER = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "decoder_metrics.py"))

METRIC = {
    "name": "moe_picks_held_per_token",
    "unit": "picks/token",
    "better": "lower",
    "source": "program_span",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    return DECODER["moe_picks_held_per_token"](run)
