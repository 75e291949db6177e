"""The share of the noisy rows that were masked, over the traced epochs:
``masked / rows`` of the program's ``fit.epoch.diffusion_mask`` records
(one a loss-head node and epoch, from the head's own counts). Its place is
``(B + 1) / (2 B)`` at a block length of ``B`` with 1 to ``B`` masks a
block: the rows that carry loss, and so the work the head's gradient is
taken over. ``better: lower`` for want of a third word. Nothing where the
program leaves no such record.
"""

import os
import runpy

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPANS = runpy.run_path(os.path.join(HERE, "span_reduce.py"))

METRIC = {
    "name": "blockdiff_masked_share",
    "unit": "ratio",
    "better": "lower",
    "source": "program_span",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    found = SPANS["program_records"]()
    if found is None:
        return None
    records = found[0]
    epochs = SPANS["traced_epochs"](records, run["rows"],
                                    run["traced_epochs"])
    if not epochs:
        return None
    wanted = {e["after"]["epoch"] for e in epochs}
    seen = [r["attrs"] for r in records
            if r["name"] == "fit.epoch.diffusion_mask"
            and r["epoch"] in wanted]
    rows = sum(a["rows"] for a in seen)
    return sum(a["masked"] for a in seen) / rows if rows else None
