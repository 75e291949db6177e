"""Set-up's compile work as spans and records: ``precompile``'s
``setup.precompile`` with its children, the three stages of
``TrackedJit.precompile`` (``setup.compile.trace`` / ``.lower`` /
``.backend``), and the one ``compile.backend`` record every other XLA
compile leaves in the span ring. CPU only; no assertion compares
host-clock times with each other, only a record's ends with its
enclosing span's.
"""

import collections
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.utils import compile as compile_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, BATCH = 48, 16
STAGES = ["setup.compile.trace", "setup.compile.lower",
          "setup.compile.backend"]
NAMED = ("fit.", "setup.", "feed.")     # every span the program opens


def _net():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _data(seed=5):
    rng = np.random.RandomState(seed)
    return (rng.randn(ROWS, 6).astype(np.float32),
            (rng.rand(ROWS) > 0.5).astype(np.float32))


def _model(num_epoch=1):
    return mx.FeedForward(_net(), ctx=mx.cpu(), num_epoch=num_epoch,
                          initializer=mx.init.Xavier(), learning_rate=0.1)


def _inside(inner, outer):
    return outer["start"] <= inner["start"] <= inner["end"] <= outer["end"]


@pytest.fixture(scope="module")
def warmed():
    """One ``precompile`` then one ``fit``; the records, the registry's
    totals and the train program's TrackedJit with its arguments."""
    telemetry.reset()
    mx.utils.reset_compile_stats()
    X, y = _data()
    model = _model()
    data = mx.io.NDArrayIter(X, y, batch_size=BATCH)
    seen = []
    original = compile_mod.TrackedJit.precompile

    def spy(self, *args, **kwargs):
        seen.append((self, args))
        return original(self, *args, **kwargs)

    compile_mod.TrackedJit.precompile = spy
    try:
        warm = model.precompile(data=data, eval_metric="ce")
    finally:
        compile_mod.TrackedJit.precompile = original
    model.fit(data, eval_metric="ce")
    return {"warm": warm, "records": telemetry.span_records(),
            "dropped": telemetry.spans_dropped(),
            "stats": mx.utils.compile_stats(), "programs": seen}


def test_precompile_nests_its_spans_and_the_three_stages(warmed):
    records = warmed["records"]
    (label,) = warmed["warm"]["labels"]
    (top,) = [r for r in records if r["name"] == "setup.precompile"]
    assert top["parent"] is None and top["attrs"] == {"programs": 1}
    children = sorted((r for r in records if r["parent"] == "setup.precompile"
                       and r["name"].startswith("setup.")),
                      key=lambda r: r["start"])
    assert [r["name"] for r in children] == [
        "setup.init_params", "setup.build_step", "setup.compile"]
    for r in children:
        assert _inside(r, top)
    init, built, whole = children
    assert init["attrs"]["arrays"] == 4
    assert built["attrs"] == {"label": label}
    assert whole["attrs"] == {"label": label}
    stages = sorted((r for r in records if r["parent"] == "setup.compile"),
                    key=lambda r: r["start"])
    assert [r["name"] for r in stages] == STAGES
    for a, b in zip(stages, stages[1:]):        # one after the other
        assert a["end"] <= b["start"]
    for r in stages:
        assert _inside(r, whole) and r["thread"] == top["thread"]
        assert r["attrs"]["label"] == label
    # tier-1 keeps no persistent cache: the backend stage says so
    assert stages[-1]["attrs"] == {"label": label, "cache": "off"}
    assert [r["attrs"] for r in stages[:2]] == [{"label": label}] * 2


def test_trace_then_lower_is_the_module_lower_gives(warmed):
    (tracked, args), = [(t, a) for t, a in warmed["programs"]
                        if t.label.startswith("train_step:")]
    through_trace = tracked.jitted.trace(*args).lower().as_text()
    assert through_trace == tracked.lower(*args).as_text()


def test_every_compile_is_timed_once_and_no_jax_trace_event_is_recorded(
        warmed):
    records = warmed["records"]
    assert warmed["dropped"] == 0
    assert all(r["name"].startswith(NAMED) or r["name"] == "compile.backend"
               for r in records), {r["name"] for r in records}
    compiles = [r for r in records if r["name"] == "compile.backend"]
    backends = [r for r in records if r["name"] == "setup.compile.backend"]
    # the train program's compile is its span's, every other one a record
    assert len(compiles) + len(backends) == warmed["stats"]["compiles"]
    assert not [r for r in compiles if r["parent"] == "setup.compile.backend"
                or r["attrs"]["label"].startswith("train_step:")]
    for r in compiles:
        assert set(r["attrs"]) == {"label", "fun", "cache"}
        assert r["attrs"]["cache"] == "off" and r["end"] >= r["start"]
    # the initializer's programs compile inside setup.init_params
    (init,) = [r for r in records if r["name"] == "setup.init_params"
               and r["parent"] == "setup.precompile"]
    under = [r for r in compiles if r["parent"] == "setup.init_params"]
    assert under and all(_inside(r, init) for r in under)


def test_one_fresh_untracked_program_adds_one_record():
    telemetry.reset()
    before = mx.utils.compile_stats()["compiles"]
    x = np.linspace(0.0, 1.0, 13, dtype=np.float32)
    out = jax.jit(lambda v: jnp.sin(v) * 2.5 + 0.75)(x)
    np.testing.assert_allclose(np.asarray(out), np.sin(x) * 2.5 + 0.75,
                               rtol=1e-6)
    assert mx.utils.compile_stats()["compiles"] == before + 1
    (record,) = telemetry.span_records()
    assert record["name"] == "compile.backend"
    assert record["attrs"]["label"] == "<untracked>"
    assert record["attrs"]["cache"] == "off"
    assert "lambda" in record["attrs"]["fun"]
    assert record["parent"] is None and record["epoch"] is None
    assert record["thread"] == "MainThread"
    # under an open span the record takes its parent, epoch and step
    with telemetry.phase("outer", epoch=3, step=9):
        jax.jit(lambda v: jnp.cos(v) - 0.25)(x)
    inner = [r for r in telemetry.span_records()
             if r["name"] == "compile.backend"][-1]
    (outer,) = [r for r in telemetry.span_records() if r["name"] == "outer"]
    assert (inner["parent"], inner["epoch"], inner["step"]) == ("outer", 3, 9)
    assert _inside(inner, outer)
    hists = telemetry.hub().snapshot()["histograms"]
    assert hists["compile.backend_seconds"]["count"] == 2


def test_a_jit_miss_inside_fit_names_its_epoch_and_step():
    """The jit caches dropped after step 1 of epoch 1: step 2's dispatch
    compiles the train program again, inside its ``fit.dispatch``."""
    telemetry.reset()

    def drop_caches(param):
        if (param.epoch, param.nbatch) == (1, 2):
            jax.clear_caches()

    X, y = _data()
    _model(num_epoch=2).fit(X, y, batch_size=BATCH, eval_metric="ce",
                            batch_end_callback=drop_caches)
    records = telemetry.span_records()
    trains = [r for r in records if r["name"] == "compile.backend"
              and r["attrs"]["label"].startswith("train_step:")]
    assert [(r["epoch"], r["step"]) for r in trains] == [(0, 0), (1, 2)]
    for r in trains:
        assert r["parent"] == "fit.dispatch"
        (dispatch,) = [d for d in records if d["name"] == "fit.dispatch"
                       and (d["epoch"], d["step"]) == (r["epoch"],
                                                       r["step"])]
        assert _inside(r, dispatch)


CACHED_RUN = """
import json, sys
import numpy as np
sys.path.insert(0, {root!r})
sys.path.insert(0, {tests!r})
import test_setup_spans as t
from mxnet_tpu import telemetry
X, y = t._data()
model = t._model()
data = t.mx.io.NDArrayIter(X, y, batch_size=t.BATCH)
model.precompile(data=data, eval_metric="ce")
model.fit(data, eval_metric="ce")
records = telemetry.span_records()
print(json.dumps({{
    "train": [r["attrs"]["cache"] for r in records
              if r["name"] == "setup.compile.backend"],
    "others": sorted({{r["attrs"]["cache"] for r in records
                      if r["name"] == "compile.backend"}})}}))
"""


def test_the_cache_attr_reads_miss_then_hit_with_a_persistent_cache(
        tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    script = CACHED_RUN.format(root=ROOT,
                               tests=os.path.join(ROOT, "tests"))
    seen = []
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              cwd=str(tmp_path), capture_output=True,
                              text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-3000:]
        seen.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    cold, warm = seen
    assert cold["train"] == ["miss"] and warm["train"] == ["hit"]
    assert "miss" in cold["others"] and warm["others"] == ["hit"]


def test_registry_listener_returns_at_once_for_other_events():
    telemetry.reset()
    reg = compile_mod.registry()
    before = reg.snapshot()
    for name in ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration"):
        reg._on_duration(name, 0.5, fun_name="f")
    assert reg.snapshot() == before and telemetry.span_records() == []
    counts = collections.Counter()
    with reg.attribute("prog"):
        reg._on_duration("/jax/core/compile/backend_compile_duration", 0.25,
                         fun_name="jit(f)")
    for r in telemetry.span_records():
        counts[r["name"], r["attrs"]["label"], r["attrs"]["fun"]] += 1
    assert counts == {("compile.backend", "prog", "jit(f)"): 1}
    assert reg.snapshot()["compiles"] == before["compiles"] + 1
