"""Model FLOP per sample (forward + backward, the algorithm's count from the
configuration's layer shapes, ``flops.py``; 2 FLOP a multiply-add) x samples
per chip per step / ``device_step_ms`` / the chip's published bf16 peak
(``peaks.json``).
"""

METRIC = {
    "name": "mfu_device",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "graph to XLA (symbol.py, executor.py, ops/)",
    "moves": "samples_per_s_per_chip",
}


def read(run):
    trace, peak = run["trace"], run["peak"]
    if not trace or not peak:
        return None
    flop = run["flops_per_sample"] * run["per_chip_batch"]
    return 100.0 * flop / (trace["device_step_ms_p50"] / 1e3) \
        / peak["bf16_flops"]
