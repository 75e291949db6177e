"""Profiler tests: the trace-digest parser against a synthesized XProf
export (deterministic), plus a live profile_step smoke on CPU (host traces
carry no per-op XLA lanes, so stats may be empty there — the parser's op
rows come from device traces)."""

import gzip
import json
import os

import numpy as np

from mxnet_tpu.utils import profiler


def _write_trace(tmp_path, events):
    d = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    os.makedirs(d)
    with gzip.open(d / "vm.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    return str(tmp_path)


def test_trace_op_stats_parses_and_aggregates(tmp_path):
    events = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 7, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 9, "tid": 1, "name": "thread_name",
         "args": {"name": "python"}},
        # two instances of the same fusion (suffix-stripped -> aggregated)
        {"ph": "X", "pid": 3, "tid": 7, "name": "fusion.12", "dur": 100},
        {"ph": "X", "pid": 3, "tid": 7, "name": "fusion.13", "dur": 50},
        {"ph": "X", "pid": 3, "tid": 7, "name": "copy.1", "dur": 30},
        # host lane events must be ignored
        {"ph": "X", "pid": 9, "tid": 1, "name": "PjitFunction(f)", "dur": 999},
    ]
    log_dir = _write_trace(tmp_path, events)
    stats = profiler.trace_op_stats(log_dir)
    assert [(s.name, s.total_us, s.count) for s in stats] == [
        ("fusion", 150, 2), ("copy", 30, 1)]
    # device filter
    assert profiler.trace_op_stats(log_dir, device_substr="TPU")
    assert not profiler.trace_op_stats(log_dir, device_substr="GPU")
    # pretty print
    assert "fusion" in str(stats[0])


def test_profile_step_smoke(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return jnp.tanh(x).sum()

    x = jnp.asarray(np.random.randn(64, 64).astype(np.float32))
    stats, log_dir = profiler.profile_step(f, x, iters=2,
                                           log_dir=str(tmp_path / "tr"))
    assert os.path.isdir(log_dir)
    assert isinstance(stats, list)  # may be empty on host-only traces


def test_timer_counts_dispatched_but_unfinished_work():
    """Regression (ISSUE 5 satellite): Timer must block on the actual
    outputs, not on jax.effects_barrier() — effects_barrier orders effects
    only and does not wait for committed pure computation on all jax pins,
    so an async-dispatched step could previously be timed at enqueue cost.
    A dispatched-but-unfinished computation must be FULLY counted."""
    import time

    import jax
    import jax.numpy as jnp

    @jax.jit
    def heavy(a):
        def body(_, x):
            return jnp.tanh(x @ a)

        return jax.lax.fori_loop(0, 40, body, a)

    a = jnp.asarray(np.random.RandomState(0).randn(512, 512)
                    .astype(np.float32))
    jax.block_until_ready(heavy(a))  # compile outside any timed window

    # ground truth: synchronous run time
    t0 = time.perf_counter()
    jax.block_until_ready(heavy(a))
    sync_s = time.perf_counter() - t0

    with profiler.Timer() as t:
        t.block(heavy(a))  # async dispatch; Timer must wait for the result
    assert t.elapsed >= 0.5 * sync_s, \
        f"Timer undercounted: {t.elapsed:.4f}s vs sync {sync_s:.4f}s"


def test_timer_block_returns_outputs_and_nests_pytrees():
    import jax.numpy as jnp

    with profiler.Timer() as t:
        out = t.block(jnp.ones(4) * 2)
        pair = t.block(jnp.zeros(2), {"a": jnp.ones(3)})
    assert float(out.sum()) == 8.0
    assert isinstance(pair, tuple) and len(pair) == 2
    assert t.elapsed >= 0.0
