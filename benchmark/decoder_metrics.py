"""Readers of the per-layer metrics of a decoder cell (window and full
attention, an expert layer): device time under an operator's scope
(``scopes.ms_per_step``), the kernels' shares of their rooflines
(``kernel_costs.py``) and the experts' load from the program's
``fit.epoch.expert_load`` records. A metric file calls one of these with
the run alone; the sizes are the cell's own, ``run["config"]`` (the
configuration's file as ``catalog.find_cell`` read it), so one metric name
serves every decoder cell that ``BENCHMARK.json`` lists under it. Each
returns ``None`` where there is nothing to read (no trace, no HLO text, a
program without the records, a configuration that is no decoder's), never 0.

Keys read from a configuration, as a published ``config.json`` writes them:
``num_hidden_layers``; ``layer_types`` (absent: every layer is
``full_attention``); ``num_attention_heads_per_layer[l]`` where present, else
``num_attention_heads``; ``num_key_value_heads``; ``head_dim``;
``sliding_window`` (null or absent: 0); ``hidden_size``;
``moe_intermediate_size``; and the harness's ``input_shape``.

Scopes (the train program's HLO, read on the chip): the executor emits the
attention operator under ``layer<l>_attn/RotaryAttention`` (forward
``jvp(...)``, backward and the recomputed forward
``transpose(jvp(jvp()))/checkpoint/[rematted_computation/]...``), its
kernels under ``.../flash_fwd/pallas_call`` and ``flash_bwd_dq`` / ``_dkv``;
the expert operator under ``layer<l>_moe/MixtureOfExperts``, except its
grouped products: XLA lowers ``jax.lax.ragged_dot`` to a kernel of its own
whose instructions (``ragged-dot-none.<n>``) carry the ``op_name``
``ragged-dot-none`` and no scope, so they are found by that name.
"""

import os
import runpy

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPES = runpy.run_path(os.path.join(HERE, "scopes.py"))
SPANS = runpy.run_path(os.path.join(HERE, "span_reduce.py"))
COSTS = runpy.run_path(os.path.join(HERE, "kernel_costs.py"))

GROUPED = r"^ragged-dot"
FLASH = r"\)?/flash_(?:fwd|bwd_dq|bwd_dkv)/pallas_call"   # jvp(<scope>)/...
FULL, SLIDING = "full_attention", "sliding_attention"


def attention_layers(cfg, kind):
    """The layers of ``kind`` (``FULL`` / ``SLIDING``); none where the
    configuration has no decoder layers."""
    depth = cfg.get("num_hidden_layers", 0)
    types = cfg.get("layer_types") or [FULL] * depth
    return [l for l in range(depth) if types[l] == kind]


def attention_scope(layers):
    return rf"layer(?:{'|'.join(map(str, layers))})_attn/RotaryAttention"


def attention_ms(run, kind):
    """Device ms a step under the attention operator's scope over the
    layers of ``kind``: rotary positions, the kernels forward and backward
    (the recomputed forward included), the gate."""
    layers = attention_layers(run["config"], kind)
    if not layers:
        return None
    return SCOPES["ms_per_step"](run, attention_scope(layers))


def attention_roofline_pct(run, kind):
    """The least time the chip could take for the attention kernels of the
    layers of ``kind``, forward and backward once each, over the time
    their kernels took in a step. The forward kernel runs twice a step
    (each decoder layer is recomputed in the backward pass): the second
    run counts in the time and not in the work."""
    cfg = run["config"]
    layers = attention_layers(cfg, kind)
    if not layers or not run.get("peak"):
        return None
    took = SCOPES["ms_per_step"](run, attention_scope(layers) + FLASH)
    if not took:
        return None
    heads = cfg.get("num_attention_heads_per_layer") \
        or [cfg["num_attention_heads"]] * cfg["num_hidden_layers"]
    window = (cfg.get("sliding_window") or 0) if kind == SLIDING else 0
    least = 0.0
    for l in layers:
        for cost in COSTS["flash_attention"](
                heads[l], cfg["num_key_value_heads"], cfg["input_shape"][0],
                cfg["head_dim"], cfg["head_dim"], window):
            least += COSTS["roofline_seconds"](cost, run["peak"])
    return 100.0 * 1e3 * least * run["per_chip_batch"] / took


def moe_ms(run):
    """Device ms a step of everything the expert operator does: router,
    top-k, sort and gathers, grouped products, combine, shared expert."""
    return SCOPES["ms_per_step"](run, r"/MixtureOfExperts|" + GROUPED)


def expert_load(run):
    """The traced epochs' ``fit.epoch.expert_load`` attributes, one entry
    a node and epoch, or ``None``."""
    found = SPANS["program_records"]()
    if found is None:
        return None
    records = found[0]
    epochs = SPANS["traced_epochs"](records, run["rows"],
                                    run["traced_epochs"])
    if not epochs:
        return None
    wanted = {e["after"]["epoch"] for e in epochs}
    return [r["attrs"] for r in records
            if r["name"] == "fit.epoch.expert_load"
            and r["epoch"] in wanted] or None


def moe_load_max_over_mean(run):
    """Over the experts held here, the largest count of picks over the
    mean, of the worst node and traced epoch."""
    loads = expert_load(run)
    if not loads:
        return None
    return max(a["max_held"] * a["experts_held"] / a["picks_held"]
               for a in loads if a["picks_held"])


def moe_picks_held_per_token(run):
    """Picks that fell on experts held here, a token and expert layer."""
    loads = expert_load(run)
    if not loads or not sum(a["tokens"] for a in loads):
        return None
    return sum(a["picks_held"] for a in loads) \
        / sum(a["tokens"] for a in loads)


def moe_grouped_roofline_pct(run):
    """The least time for the grouped products of every expert layer,
    forward and backward once each, at the rows a step of the traced
    epochs really routed here, over the time the grouped products took in
    a step (the recomputed forward's included in the time only)."""
    took = SCOPES["ms_per_step"](run, GROUPED)
    loads = expert_load(run)
    cfg = run["config"]
    if not took or not loads or not run.get("peak") \
            or "moe_intermediate_size" not in cfg:
        return None
    least = 0.0
    for a in loads:          # one entry a node and traced epoch
        if not a["picks_held"]:
            continue
        # the weights charged are those of the experts that had a pick
        # in the epoch (``experts_hit``, counted by the operator): an
        # expert without rows is not read
        for products in COSTS["gated_experts"](
                a["picks_held"] / run["steps_per_epoch"], cfg["hidden_size"],
                cfg["moe_intermediate_size"], a["experts_hit"]):
            least += sum(COSTS["roofline_seconds"](cost, run["peak"])
                         for cost in products)
    return 100.0 * 1e3 * least / len(run["traced_epochs"]) / took
