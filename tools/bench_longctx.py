"""Single-chip long-context train-step sweep for the Transformer LM.

Long-context is a first-class capability of this framework (SURVEY.md §5;
the reference's longest sequences are PTB bucket lengths,
/root/reference/example/rnn/lstm_ptb.py) — this measures it ON HARDWARE:
one full train step (fwd + bwd + SGD-momentum update, bf-free f32
params, flash attention auto-selected on TPU) across sequence lengths,
with and without per-layer rematerialization (``remat=True`` =
``jax.checkpoint`` per decoder layer, models/transformer.py).

What the sweep demonstrates:
- the flash kernel keeps attention linear-memory, so single-chip context
  scales to tens of k tokens (the O(seq²) dense path would OOM first);
- remat trades ~one extra forward of FLOPs for saved-activation memory —
  the knob that extends reachable context further (an OOM at the longest
  no-remat length that *passes* with remat is the designed outcome, and
  is recorded rather than failing the sweep);
- tokens/s per config, slope-timed (in-device
  fori_loop on CHAINED state, slope between two run lengths — same
  rationale as tools/bench_flash.py).

Writes LONGCTX_r<N>.json: one record per (seq, remat) with step ms,
tokens/s, and oom flag.

Run: python tools/bench_longctx.py --out LONGCTX_r05.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fence(x):
    import jax.numpy as jnp
    return float(jnp.sum(x))


def bench_config(seq, remat, d_model=512, n_layers=4, vocab=8192, iters=4):
    """-> dict record. OOM is caught and recorded, not raised."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models.transformer import (TransformerLM,
                                              transformer_lm_config)

    cfg = transformer_lm_config(vocab_size=vocab, d_model=d_model,
                                n_heads=d_model // 64, n_layers=n_layers,
                                d_ff=4 * d_model, max_len=seq, remat=remat)
    model = TransformerLM(cfg)
    rec = {"seq": seq, "remat": bool(remat), "d_model": d_model,
           "n_layers": n_layers, "batch": 1}
    try:
        params, moms = model.init_sharded(None)
        step = model.make_train_step(None, lr=1e-3)
        key = jax.random.PRNGKey(0)
        tokens = jax.random.randint(key, (1, seq), 0, vocab, jnp.int32)
        targets = jnp.roll(tokens, -1, axis=1)

        # the loop must chain state; tokens/targets stay constant
        def body(_, st):
            p, m, _ = step(st[0], st[1], tokens, targets)
            return (p, m, jnp.zeros(()))

        @jax.jit
        def run(p, m, k):
            return jax.lax.fori_loop(
                0, k, body, (p, m, jnp.zeros(())))

        k1, k2 = iters, iters * 3
        p, m, _ = run(params, moms, k1)          # compile + warm
        _fence(p["embed"])
        t0 = time.perf_counter()
        p, m, _ = run(p, m, k1)
        _fence(p["embed"])
        t1 = time.perf_counter()
        p, m, _ = run(p, m, k2)
        _fence(p["embed"])
        t2 = time.perf_counter()
        per_iter = ((t2 - t1) - (t1 - t0)) / (k2 - k1)
        rec.update(step_ms=round(per_iter * 1e3, 2),
                   tokens_per_sec=round(seq / per_iter, 1), oom=False)
    except Exception as e:  # RESOURCE_EXHAUSTED etc. — record and move on
        msg = str(e)
        rec.update(oom="RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg,
                   error=msg[:200])
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="LONGCTX_r05.json")
    ap.add_argument("--seqs", default="2048,8192,16384,32768")
    ap.add_argument("--iters", type=int, default=4)
    args = ap.parse_args()

    import jax
    print("backend:", jax.default_backend(), jax.devices())

    records = []
    for seq in (int(s) for s in args.seqs.split(",")):
        for remat in (False, True):
            rec = bench_config(seq, remat, iters=args.iters)
            print(json.dumps(rec))
            records.append(rec)

    out = {"device": str(jax.devices()[0]),
           "model": "TransformerLM d=512 L=4 flash-auto b1 full train step",
           "timing": "in-device fori_loop, chained state, slope-timed",
           "records": records}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
