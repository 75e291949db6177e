"""Roofline evidence for the ResNet-50 train step.

Round 2 left ~45 ms of the 103 ms b256 step attributed to "backward
elementwise / optimizer fusions" with every attempted reformulation flat —
but flat-vs-alternatives is not the same as *bandwidth-bound*. This tool
produces the missing quantitative comparison:

1. measured achievable HBM bandwidth on this chip (triad-style kernel:
   read 2 arrays, write 1, through the same fori_loop slope timing as
   bench.py, so dispatch constants cancel);
2. the train step's actual HBM traffic, from XLA's cost analysis of the
   exact compiled step (bytes accessed);
3. the implied memory-bound step-time floor  traffic / bandwidth  vs the
   measured step time.

If measured step time is within ~15% of the floor, the step is
bandwidth-bound and the remaining gap to matmul peak is not recoverable by
elementwise tinkering (doc/performance.md gets the table). Otherwise the
difference bounds the recoverable headroom.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time

import numpy as np

sys.path.insert(0, ".")

from bench import (build_resnet50_train_step, _data_shape,  # noqa: E402
                   measured_matmul_peak_tflops)


def measured_hbm_bandwidth_gbs(mb=256, iters=16, samples=3):
    """Achievable HBM bandwidth: streaming copy kernel (x -> -x), 1 read +
    1 write per element, chained in-device (fori_loop slope method, median
    of samples). Measured 633 GB/s on this chip vs the 819 GB/s v5e spec;
    a 2-read-1-write triad variant measures only ~290 GB/s (dual-stream
    reads defeat the prefetcher here), so copy is the honest 'achievable'
    number for the roofline."""
    import jax
    import jax.numpy as jnp

    n = mb * (1 << 20) // 4
    a = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)

    @jax.jit
    def run(x, k):
        return jax.lax.fori_loop(0, k, lambda i, v: -v, x)

    k1, k2 = iters, iters * 4
    a = run(a, k1)
    float(jnp.sum(a[:8]))
    rates = []
    for _ in range(samples):
        t0 = time.perf_counter()
        a = run(a, k1)
        float(jnp.sum(a[:8]))
        t1 = time.perf_counter()
        a = run(a, k2)
        float(jnp.sum(a[:8]))
        t2 = time.perf_counter()
        per_iter = ((t2 - t1) - (t1 - t0)) / (k2 - k1)
        rates.append(2 * n * 4 / per_iter / 1e9)
    rates.sort()
    return rates[len(rates) // 2]


def analytic_min_traffic_gb(batch_size):
    """First-principles minimum HBM traffic for the train step.

    Every node-output activation of the graph (bf16) must cross HBM at
    least ~3 times in a perfectly fused training step: written once in
    forward, read once by its consumer's backward (rematerialized relu
    masks notwithstanding), and its gradient written+consumed within a
    fusion (≈1 more crossing amortized). Parameters + grads + momentum add
    ~6 crossings of the f32 param bytes. This is the IDEAL-fusion floor;
    XLA's cost-analysis 'bytes accessed' of the real compiled step is the
    matching upper accounting (each fusion's operands+outputs, no cache
    modeling)."""
    import numpy as np

    from mxnet_tpu.models import resnet50

    sym = resnet50(num_classes=1000, layout="NHWC")
    internals = sym.get_internals()
    outs = internals.list_outputs()
    arg_shapes, _, _ = sym.infer_shape(data=(batch_size, 224, 224, 3),
                                       softmax_label=(batch_size,))
    _, ishapes, _ = internals.infer_shape(data=(batch_size, 224, 224, 3),
                                          softmax_label=(batch_size,))
    act = sum(int(np.prod(s)) * 2 for n, s in zip(outs, ishapes)
              if n.endswith("_output"))
    params = sum(int(np.prod(s)) * 4
                 for n, s in zip(sym.list_arguments(), arg_shapes)
                 if n not in ("data", "softmax_label"))
    return (3 * act + 6 * params) / 1e9


def step_traffic_bytes(batch_size, layout="NHWC"):
    """HBM bytes accessed by the exact compiled train step, from XLA's cost
    analysis ('bytes accessed' = the compiler's own traffic model)."""
    import jax

    step, params, moms, aux = build_resnet50_train_step(batch_size,
                                                        layout=layout)
    rng = np.random.RandomState(0)
    data = jax.device_put(rng.randn(
        *_data_shape(batch_size, layout)).astype(np.float32))
    label = jax.device_put(
        rng.randint(0, 1000, (batch_size,)).astype(np.float32))
    compiled = step.lower(params, moms, aux, data, label).compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    return ({k: float(v) for k, v in ca.items()
             if isinstance(v, (int, float)) and ("bytes" in k or k == "flops")},
            compiled, step, params, moms, aux, data, label)


_SHAPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8,
                "s16": 2, "u16": 2}


def _shape_nbytes(shape_str):
    """Bytes of one HLO shape token like 'bf16[256,56,56,64]{3,2,1,0}'
    (layout suffix ignored; tuples handled by the caller)."""
    m = re.match(r"([a-z]+\d*)\[([\d,]*)\]", shape_str)
    if not m:
        return 0
    elem = _SHAPE_BYTES.get(m.group(1), 4)
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    return elem * n


def per_op_bytes_table(compiled, top_k=25):
    """Rank the compiled step's instructions by HBM bytes accessed
    (makes the traffic excess over the analytic minimum attributable op
    by op).

    XLA's aggregate 'bytes accessed' cost model charges each instruction
    its operand bytes + output bytes (no cache modeling). The optimized
    HLO text carries every instruction's output shape inline and its
    operands by name, so the same accounting is reproducible per
    instruction: parse name -> output shape, then charge each non-trivial
    instruction sum(operand shapes) + output shape. Fusions are single
    instructions here — exactly the granularity at which HBM traffic
    happens on TPU (one fusion = one read of its operands + one write of
    its outputs).

    Returns (rows, totals_by_opcode): rows = [{name, opcode, gbytes,
    source, shape}] sorted desc — ``source`` is the XLA metadata op_name
    path (model-layer attribution; None when absent, tail-truncated to 80
    chars)."""
    hlo = compiled.as_text()
    # ENTRY computation only: fusion bodies (%fused_computation.N { ... })
    # list their internal elementwise ops with the same line shape, but
    # those never touch HBM — the enclosing fusion instruction in ENTRY is
    # the HBM-traffic unit. Counting bodies would double-charge massively.
    entry_lines = []
    in_entry = False
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            in_entry = True
            continue
        if in_entry and line.startswith("}"):
            break
        if in_entry:
            entry_lines.append(line)
    # name -> output nbytes (tuple shapes: sum of leaves)
    out_bytes = {}
    inst_re = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|[a-z]+\d*\[[^\]]*\]"
        r"(?:\{[^}]*\})?)\s+([\w\-]+)\(")
    insts = []
    for line in entry_lines:
        m = inst_re.match(line)
        if not m:
            continue
        name, shape_s, opcode = m.groups()
        if shape_s.startswith("("):
            nbytes = sum(_shape_nbytes(s) for s in
                         re.findall(r"[a-z]+\d*\[[\d,]*\]", shape_s))
        else:
            nbytes = _shape_nbytes(shape_s)
        out_bytes[name] = nbytes
        # m.end() sits just past the CALL's opening paren (inst_re ends
        # with \() — the only safe operand-scan anchor: tuple OUTPUT
        # shapes put earlier parens on the line
        insts.append((name, opcode, nbytes, shape_s, line, m.end()))
    # charge operands: tokens inside the call parens that name an ENTRY
    # instruction (sigil-robust: newer XLA dumps omit the % prefix — the
    # out_bytes membership test is what identifies operand references).
    # parameter/constant/gte lines carry no traffic of their own (gte is
    # a view; parameters are charged when a consumer reads them).
    skip = {"parameter", "constant", "get-tuple-element", "tuple",
            "bitcast"}
    rows = []
    for name, opcode, nbytes, shape_s, line, body_start in insts:
        if opcode in skip:
            continue
        body = line[body_start:]
        # operands live in the argument list only: cut at the call's
        # balanced closing paren (structural, not a marker list) so tokens
        # in attribute tails — metadata op_name paths, window=, dim_labels=
        # — can never be charged as phantom operands of this instruction.
        # Tuple-typed operands nest parens; track depth.
        depth = 0
        for i, ch in enumerate(body):
            if ch == "(":
                depth += 1
            elif ch == ")":
                if depth == 0:
                    body = body[:i]
                    break
                depth -= 1
        ops = [t for t in re.findall(r"%?([\w.\-]+)", body)
               if t in out_bytes]
        total = nbytes + sum(out_bytes[o] for o in ops)
        # source attribution: XLA metadata carries the jax op_name path
        # (e.g. ".../bn4c/batch_norm"), which maps the fusion back to the
        # model layer that produced it
        meta = re.search(r'op_name="([^"]*)"', line)
        rows.append({"name": name, "opcode": opcode,
                     "gbytes": total / 1e9,
                     "source": (meta.group(1)[-80:] if meta else None),
                     "shape": shape_s if len(shape_s) < 64 else
                     shape_s[:61] + "..."})
    rows.sort(key=lambda r: -r["gbytes"])
    totals = {}
    for r in rows:
        totals[r["opcode"]] = totals.get(r["opcode"], 0.0) + r["gbytes"]
    totals = dict(sorted(totals.items(), key=lambda kv: -kv[1]))
    return rows[:top_k], totals


def timed_step_ms(step, params, moms, aux, data, label, steps=16):
    import jax
    import jax.numpy as jnp

    def loop_step(s):
        p, m, a = step(s[0], s[1], s[2], data, label)
        return (p, m, a)

    @jax.jit
    def run(s, k):
        return jax.lax.fori_loop(0, k, lambda i, t: loop_step(t), s)

    k1, k2 = max(2, steps // 4), steps
    state = (params, moms, aux)
    state = run(state, k1)
    float(jnp.sum(state[0]["fc1_bias"]))
    t0 = time.perf_counter()
    state = run(state, k1)
    float(jnp.sum(state[0]["fc1_bias"]))
    t1 = time.perf_counter()
    state = run(state, k2)
    float(jnp.sum(state[0]["fc1_bias"]))
    t2 = time.perf_counter()
    return ((t2 - t1) - (t1 - t0)) / (k2 - k1) * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--out", default="ROOFLINE_r05.json")
    ap.add_argument("--analyze-only", action="store_true",
                    help="compile + per-op traffic table only (no timed "
                         "runs; usable on the CPU backend)")
    ap.add_argument("--remat", nargs="?", const=r"unit\d+_out$", default="",
                    help="apply MXNET_TPU_REMAT before compiling, to "
                         "compare saved-activation traffic vs the inline "
                         "step (bare --remat = ResNet unit boundaries)")
    ap.add_argument("--jaxpr-table", action="store_true",
                    help="also print mxlint Pass-3 per-primitive FLOP/byte "
                         "totals from the pre-fusion jaxpr (brackets the "
                         "HLO table from the unfused side)")
    args = ap.parse_args()

    import os

    if args.remat:
        os.environ["MXNET_TPU_REMAT"] = args.remat

    import jax

    if not args.analyze_only:
        bw = measured_hbm_bandwidth_gbs()
        print(f"measured HBM triad bandwidth: {bw:.0f} GB/s")

    costs, compiled, step, params, moms, aux, data, label = \
        step_traffic_bytes(args.batch_size)
    traffic = costs.get("bytes accessed", 0.0)
    print(f"XLA bytes accessed per step: {traffic/1e9:.2f} GB")

    top_rows, op_totals = per_op_bytes_table(compiled)
    print("top HBM-traffic instructions (operand+output bytes):")
    for r in top_rows[:15]:
        src = f"  <- {r['source']}" if r.get("source") else ""
        print(f"  {r['gbytes']:7.3f} GB  {r['opcode']:<22} "
              f"{r['name']}{src}")
    print("traffic by opcode:",
          {k: round(v, 2) for k, v in list(op_totals.items())[:8]})

    if args.jaxpr_table:
        from mxnet_tpu.analysis import cost_rows

        rows, totals = cost_rows(step, params, moms, aux, data, label)
        print(f"jaxpr (pre-fusion): {totals['eqns']} eqns, "
              f"{totals['flops']/1e9:.2f} GFLOP, "
              f"{totals['bytes']/1e9:.2f} GB unfused operand+output bytes")
        for r in rows[:15]:
            print(f"  {r['bytes']/1e9:7.3f} GB  {r['flops']/1e9:8.3f} GF  "
                  f"{r['primitive']:<24} x{r['count']}")

    if args.analyze_only:
        out = {
            "batch_size": args.batch_size,
            "remat": os.environ.get("MXNET_TPU_REMAT") or None,
            "xla_bytes_accessed_gb": round(traffic / 1e9, 3),
            "analytic_min_traffic_gb": round(
                analytic_min_traffic_gb(args.batch_size), 2),
            "per_op_top": top_rows,
            "per_opcode_gb": {k: round(v, 3) for k, v in op_totals.items()},
        }
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.out} (analyze-only)")
        return

    ms = timed_step_ms(step, params, moms, aux, data, label)
    peak = measured_matmul_peak_tflops()

    ideal_gb = analytic_min_traffic_gb(args.batch_size)
    floor_ideal_ms = ideal_gb / bw * 1e3
    floor_xla_ms = traffic / (bw * 1e9) * 1e3
    flops = costs.get("flops", 0.0)
    floor_flops_ms = flops / (peak * 1e12) * 1e3
    out = {
        "batch_size": args.batch_size,
        "remat": os.environ.get("MXNET_TPU_REMAT") or None,
        "measured_step_ms": round(ms, 2),
        "measured_hbm_bw_gbs": round(bw, 1),
        "measured_matmul_peak_tflops": round(peak, 1),
        "analytic_min_traffic_gb": round(ideal_gb, 2),
        "xla_bytes_accessed_gb": round(traffic / 1e9, 3),
        "xla_flops_g": round(flops / 1e9, 1),
        "memory_floor_ideal_fusion_ms": round(floor_ideal_ms, 2),
        "memory_floor_xla_traffic_ms": round(floor_xla_ms, 2),
        "compute_floor_ms_at_matmul_peak": round(floor_flops_ms, 2),
        "step_vs_ideal_memory_floor": round(ms / floor_ideal_ms, 3),
        "per_op_top": top_rows,
        "per_opcode_gb": {k: round(v, 3) for k, v in op_totals.items()},
        "verdict": (
            "bandwidth-bound: memory floors (ideal %.0f ms / xla-traffic "
            "%.0f ms) dominate the %.0f ms compute floor; measured step is "
            "%.0f%% above the ideal-fusion memory floor"
            % (floor_ideal_ms, floor_xla_ms, floor_flops_ms,
               (ms / floor_ideal_ms - 1) * 100)),
    }
    print(json.dumps(out, indent=1))
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
