"""Compile-management subsystem tests (ISSUE 3 acceptance): zero-recompile
steady state with the tracker ARMED (including the tail batch), per-bucket
exactly-one-compile, persistent-cache reuse across a subprocess, pad-policy
numerical parity vs unpadded, AOT warmup, and the registry counters."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu.io import DataBatch, DataIter
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.utils import compile as cm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(num_classes=2):
    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, name="fc1", num_hidden=16)
    net = sym.Activation(data=net, name="relu1", act_type="relu")
    net = sym.FullyConnected(data=net, name="fc2", num_hidden=num_classes)
    return sym.SoftmaxOutput(data=net, name="softmax")


def _blobs(n=100, dim=10, seed=0):
    rng = np.random.RandomState(seed)
    X = np.concatenate([rng.randn(n // 2, dim) + 1,
                        rng.randn(n - n // 2, dim) - 1]).astype(np.float32)
    y = np.concatenate([np.zeros(n // 2), np.ones(n - n // 2)]).astype(
        np.float32)
    order = rng.permutation(n)
    return X[order], y[order]


class ShortTailIter(DataIter):
    """Yields full batches then one genuinely SHORT tail batch (the shape
    that silently compiles a second program without a pad policy)."""

    def __init__(self, X, y, batch_size):
        super().__init__()
        self.X, self.y = X, y
        self.batch_size = batch_size
        self.reset()

    def reset(self):
        self._i = 0

    @property
    def provide_data(self):
        return [("data", (self.batch_size,) + self.X.shape[1:])]

    @property
    def provide_label(self):
        return [("softmax_label", (self.batch_size,))]

    def next(self):
        s = self._i * self.batch_size
        if s >= len(self.X):
            raise StopIteration
        e = min(s + self.batch_size, len(self.X))
        self._i += 1
        return DataBatch([NDArray(self.X[s:e])], [NDArray(self.y[s:e])],
                         pad=0)


# -- PadPolicy units -----------------------------------------------------------

def test_pad_policy_round_rows_and_lengths():
    bucket = cm.PadPolicy("bucket")
    assert bucket.round_rows(20, 40) == 40
    assert bucket.round_rows(40, 40) == 40
    assert bucket.round_rows(50, 40) == 50  # never truncates
    assert bucket.round_length(5, [4, 8, 16]) == 8
    assert bucket.round_length(17, [4, 8, 16]) is None  # too long -> dropped

    pow2 = cm.PadPolicy("pow2")
    assert pow2.round_rows(20, 40) == 32
    assert pow2.round_rows(33, 40) == 40  # clamped to the batch size
    assert pow2.round_length(5) == 8
    assert pow2.round_length(8) == 8
    assert pow2.round_length(9, [4, 8, 16]) == 16
    assert pow2.round_length(30, [4, 8, 16]) is None

    with pytest.raises(mx.base.MXNetError):
        cm.PadPolicy("nope")


def test_pad_policy_resolve_and_env(monkeypatch):
    assert cm.PadPolicy.resolve(None) is None
    assert cm.PadPolicy.resolve(True).mode == "bucket"
    assert cm.PadPolicy.resolve("pow2").mode == "pow2"
    p = cm.PadPolicy("bucket")
    assert cm.PadPolicy.resolve(p) is p
    monkeypatch.setenv("MXNET_TPU_PAD_POLICY", "pow2")
    assert cm.PadPolicy.resolve(None).mode == "pow2"
    monkeypatch.setenv("MXNET_TPU_PAD_POLICY", "0")
    assert cm.PadPolicy.resolve(None) is None


def test_pad_policy_pad_arrays():
    p = cm.PadPolicy("bucket")
    arrays = {"data": np.arange(12, dtype=np.float32).reshape(3, 4),
              "label": np.array([1.0, 2.0, 3.0], np.float32)}
    out, valid = p.pad_arrays(arrays, 5, pad=1)
    assert valid == 2  # 3 rows minus 1 iterator-reported wrap row
    assert out["data"].shape == (5, 4)
    np.testing.assert_array_equal(out["data"][3], out["data"][2])
    np.testing.assert_array_equal(out["label"], [1, 2, 3, 3, 3])
    # already full: unchanged, same objects
    same, valid2 = p.pad_arrays(arrays, 3)
    assert same is arrays and valid2 == 3


# -- tracked jit + registry ----------------------------------------------------

def test_tracked_jit_counters_and_aot():
    import jax
    import jax.numpy as jnp

    cm.reset_compile_stats()
    f = cm.tracked_jit(lambda x: (x * 2).sum(), label="unit:double")
    f(jnp.ones((8,)))           # miss (compiles)
    f(jnp.ones((8,)))           # hit
    f(jnp.ones((4,)))           # miss (new shape)
    stats = cm.compile_stats()["per_function"]["unit:double"]
    assert stats["misses"] == 2 and stats["hits"] == 1

    # AOT: precompile a third shape, then dispatch it — no jit-cache miss
    f.precompile(jax.ShapeDtypeStruct((2,), jnp.float32))
    assert f.aot_programs == 1
    out = f(jnp.ones((2,)))
    assert float(out) == 4.0
    stats = cm.compile_stats()["per_function"]["unit:double"]
    assert stats["misses"] == 2  # unchanged: the AOT executable served it
    assert stats["aot_hits"] == 1 and stats["precompiles"] == 1


def test_recompile_tracker_raises_when_armed():
    import jax.numpy as jnp

    f = cm.tracked_jit(lambda x: x + 1, label="unit:inc")
    f(jnp.ones((3,)))  # warm
    with cm.RecompileTracker(raise_on_recompile=True):
        f(jnp.ones((3,)))  # cached: fine
        with pytest.raises(cm.RecompileError):
            f(jnp.ones((5,)))  # new shape while armed
    # disarmed again: new shapes are fine
    f(jnp.ones((7,)))

    tr = cm.RecompileTracker().arm()
    f(jnp.ones((9,)))
    tr.disarm()
    assert len(tr.recompiles) == 1
    with pytest.raises(cm.RecompileError):
        tr.assert_no_recompiles()


def test_graph_fingerprint_tracks_fusion_flags(monkeypatch):
    net = _mlp()
    fp1 = cm.graph_fingerprint(net)
    assert fp1 == cm.graph_fingerprint(net)
    monkeypatch.setenv("MXNET_TPU_FUSE", "0")
    assert cm.graph_fingerprint(net) != fp1


# -- the armed steady-state invariant (acceptance criterion) -------------------

def test_fit_zero_recompiles_steady_state_with_tail_batch():
    """THE acceptance test: a steady-state epoch — including a genuinely
    short tail batch — performs ZERO tracked compiles once warm, enforced
    by an armed RecompileTracker that raises on violation."""
    X, y = _blobs(100)
    it = ShortTailIter(X, y, 40)  # 40 + 40 + 20-row tail
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=3,
                           learning_rate=0.5)
    tracker = cm.RecompileTracker(raise_on_recompile=True)

    def arm_after_first(epoch, *_):
        if epoch == 0:
            tracker.arm()  # epoch 0 warmed every program; none may compile

    try:
        model.fit(it, batch_size=40, pad_policy="bucket",
                  epoch_end_callback=arm_after_first)
    finally:
        tracker.disarm()
    assert tracker.recompiles == []
    acc = (model.predict(X, batch_size=40).argmax(axis=1) == y).mean()
    assert acc > 0.9, acc


def test_fit_without_pad_policy_does_recompile_tail():
    """Control: the same short-tail epoch WITHOUT the policy compiles a
    second program for the odd shape (the bug the policy fixes)."""
    cm.reset_compile_stats()
    X, y = _blobs(100)
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=1,
                           learning_rate=0.5)
    model.fit(ShortTailIter(X, y, 40), batch_size=40)
    per = cm.compile_stats()["per_function"]
    train = [c for label, c in per.items() if label.startswith("train_step:")]
    assert train and train[0]["misses"] == 2  # 40-shape AND 20-shape


def test_pad_policy_numerical_parity_vs_unpadded():
    """Padded+masked tail batch == genuinely short tail batch, exactly:
    same parameter trajectory (masked loss heads inject zero gradient for
    pad rows), same final metric."""
    X, y = _blobs(100, seed=3)

    def train(pad_policy):
        np.random.seed(0)
        mx.random.seed(0)
        model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=2,
                               learning_rate=0.5,
                               initializer=mx.init.Xavier())
        model.fit(ShortTailIter(X, y, 40), batch_size=40,
                  pad_policy=pad_policy)
        return model

    a = train("bucket")
    b = train(None)
    for k in a.arg_params:
        np.testing.assert_allclose(
            a.arg_params[k].asnumpy(), b.arg_params[k].asnumpy(),
            rtol=1e-5, atol=1e-6, err_msg=k)
    sa = a.score(mx.io.NDArrayIter(X, y, batch_size=50))
    sb = b.score(mx.io.NDArrayIter(X, y, batch_size=50))
    assert abs(sa - sb) < 1e-6


def test_masked_loss_grads_match_unpadded():
    """Direct gradient check: grads from a padded batch with a validity
    mask equal grads from the unpadded batch, for every maskable loss head."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.executor import _build_graph_fn

    rng = np.random.RandomState(0)
    for head in ("SoftmaxOutput", "LinearRegressionOutput",
                 "MAERegressionOutput", "LogisticRegressionOutput"):
        data = sym.Variable("data")
        net = sym.FullyConnected(data=data, name="fc", num_hidden=2)
        net = getattr(sym, head)(data=net, name="out")
        fn = _build_graph_fn(net, is_train=True)
        w = jnp.asarray(rng.randn(2, 6).astype(np.float32))
        b = jnp.asarray(np.zeros(2, np.float32))
        x4 = jnp.asarray(rng.randn(4, 6).astype(np.float32))
        lab4 = jnp.asarray(rng.randint(0, 2, (4, 2)).astype(np.float32))
        if head == "SoftmaxOutput":
            lab4 = jnp.asarray(rng.randint(0, 2, (4,)).astype(np.float32))
        zero = jnp.zeros((2,), jnp.uint32)

        def loss(w, b, x, lab, mask=None):
            args = {"data": x, "fc_weight": w, "fc_bias": b,
                    "out_label": lab}
            outs, _ = fn(args, {}, zero, mask)
            return sum(jnp.sum(o) for o in outs)

        g_ref = jax.grad(loss, argnums=(0, 1))(w, b, x4, lab4)
        # pad to 8 rows (repeat last) + mask out the pad
        x8 = jnp.concatenate([x4, jnp.tile(x4[-1:], (4,) + (1,) * (x4.ndim - 1))])
        lab8 = jnp.concatenate([lab4, jnp.tile(lab4[-1:],
                                               (4,) + (1,) * (lab4.ndim - 1))])
        mask = jnp.asarray([1, 1, 1, 1, 0, 0, 0, 0], jnp.float32)
        g_pad = jax.grad(loss, argnums=(0, 1))(w, b, x8, lab8, mask)
        for gr, gp in zip(g_ref, g_pad):
            np.testing.assert_allclose(np.asarray(gr), np.asarray(gp),
                                       rtol=1e-5, atol=1e-6, err_msg=head)


def test_fit_pad_policy_with_guards():
    """Pad policy composes with the resilience step guards (both extend the
    step signature; ordering must hold)."""
    X, y = _blobs(60)
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=2,
                           learning_rate=0.5)
    model.fit(ShortTailIter(X, y, 25), batch_size=25, pad_policy="bucket",
              guards=True)
    acc = (model.predict(X, batch_size=25).argmax(axis=1) == y).mean()
    assert acc > 0.9, acc


# -- bucketing: per-bucket exactly-one-compile + pow2 assignment ---------------

def test_bucketing_exactly_one_compile_per_bucket():
    from mxnet_tpu.models import lstm_unroll

    def sentences(n=48):
        rng = np.random.RandomState(0)
        out = []
        for _ in range(n):
            length = int(rng.choice([3, 4, 6, 7]))
            start = int(rng.randint(1, 8))
            s = [start]
            for _ in range(length - 1):
                s.append(s[-1] % 7 + 1)
            out.append(s)
        return out

    def sym_gen(seq_len):
        return lstm_unroll(num_layers=1, seq_len=seq_len, input_size=8,
                           num_hidden=8, num_embed=4, num_label=8)

    cm.reset_compile_stats()
    init_states = [("l0_init_c", (8, 8)), ("l0_init_h", (8, 8))]
    it = mx.BucketSentenceIter(sentences(), buckets=[4, 8], batch_size=8,
                               init_states=init_states, shuffle=True)
    model = mx.BucketingFeedForward(sym_gen, default_bucket_key=8,
                                    num_epoch=3, optimizer="adam",
                                    learning_rate=0.02,
                                    initializer=mx.init.Xavier())
    tracker = cm.RecompileTracker(raise_on_recompile=True)

    def arm_after_first(epoch, *_):
        if epoch == 0:
            tracker.arm()

    try:
        model.fit(it, batch_size=8, epoch_end_callback=arm_after_first)
    finally:
        tracker.disarm()
    per = cm.compile_stats()["per_function"]
    train = {label: c for label, c in per.items()
             if label.startswith("train_step:")}
    assert len(train) == 2, sorted(train)  # one program per bucket
    for label, c in train.items():
        assert c["misses"] == 1, (label, c)  # compiled exactly once
        assert c["programs"] == 1, (label, c)


def test_bucket_sentence_iter_pow2_policy():
    sents = [[1] * 3, [1] * 5, [1] * 9, [1] * 15, [1] * 16]
    it = mx.BucketSentenceIter(sents, buckets=None, batch_size=2,
                               pad_policy="pow2")
    assert it.buckets == [4, 8, 16]
    assert it.discarded == 0
    # smallest pow2 bucket >= each length
    sizes = {b: len(m) for b, m in it._data.items()}
    assert sizes == {4: 1, 8: 1, 16: 3}
    # explicit buckets still honored under pow2 (clamped into the list)
    it2 = mx.BucketSentenceIter(sents, buckets=[4, 16], batch_size=2,
                                pad_policy="pow2")
    assert {b: len(m) for b, m in it2._data.items()} == {4: 1, 16: 4}
    # without a policy, buckets=None is an error
    with pytest.raises(ValueError):
        mx.BucketSentenceIter(sents, buckets=None, batch_size=2)


# -- AOT warmup ----------------------------------------------------------------

def test_feedforward_precompile_then_fit_no_compiles():
    X, y = _blobs(80)
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=2,
                           learning_rate=0.5)
    out = model.precompile(data_shapes={"data": (40, 10)},
                           label_shapes={"softmax_label": (40,)})
    assert out["programs"] == 1
    with cm.RecompileTracker(raise_on_recompile=True):
        model.fit(X, y, batch_size=40)
    acc = (model.predict(X, batch_size=40).argmax(axis=1) == y).mean()
    assert acc > 0.9, acc


def test_bucketing_precompile_from_iterator():
    from mxnet_tpu.models import lstm_unroll

    sents = [[1, 2, 3], [2, 3, 4, 5, 6, 7], [3, 4], [1] * 7] * 4

    def sym_gen(seq_len):
        return lstm_unroll(num_layers=1, seq_len=seq_len, input_size=8,
                           num_hidden=8, num_embed=4, num_label=8)

    init_states = [("l0_init_c", (4, 8)), ("l0_init_h", (4, 8))]
    it = mx.BucketSentenceIter(sents, buckets=[4, 8], batch_size=4,
                               init_states=init_states, shuffle=False)
    shapes = it.bucket_shapes()
    assert [b for b, _, _ in shapes] == [4, 8]
    assert shapes[0][1]["t0_data"] == ((4,), np.int32)
    assert shapes[0][1]["l0_init_c"] == (4, 8)
    model = mx.BucketingFeedForward(sym_gen, default_bucket_key=8,
                                    num_epoch=1, learning_rate=0.1,
                                    initializer=mx.init.Xavier())
    out = model.precompile(data=it)
    assert out["programs"] == 2
    with cm.RecompileTracker(raise_on_recompile=True):
        model.fit(it, batch_size=4)


def test_executor_precompile():
    cm.reset_compile_stats()
    net = _mlp()
    exe = net.simple_bind(mx.cpu(), data=(4, 10), softmax_label=(4,))
    secs = exe.precompile(is_train=False)
    assert secs >= 0.0
    exe.arg_dict["data"][:] = np.random.randn(4, 10)
    exe.arg_dict["fc1_weight"][:] = np.random.uniform(-1, 1, (16, 10))
    exe.arg_dict["fc2_weight"][:] = np.random.uniform(-1, 1, (2, 16))
    with cm.RecompileTracker(raise_on_recompile=True):
        exe.forward()
    label = exe._label("fwd_eval")
    stats = cm.compile_stats()["per_function"][label]
    assert stats["precompiles"] == 1 and stats["aot_hits"] == 1
    # train path (residual capture) precompiles too, then backward works
    exe.precompile(is_train=True)
    with cm.RecompileTracker(raise_on_recompile=True):
        exe.forward(is_train=True)
    exe.backward()
    assert exe.grad_dict["fc1_weight"].asnumpy().any()


# -- persistent cache across processes (acceptance criterion) ------------------

_CHILD = r"""
import json, os, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu.utils import compile as cm

data = sym.Variable("data")
net = sym.FullyConnected(data=data, name="fc1", num_hidden=37)
net = sym.Activation(data=net, name="r", act_type="relu")
net = sym.FullyConnected(data=net, name="fc2", num_hidden=2)
net = sym.SoftmaxOutput(data=net, name="softmax")
X = np.random.RandomState(0).randn(64, 11).astype(np.float32)
y = (X[:, 0] > 0).astype(np.float32)
model = mx.FeedForward(net, ctx=mx.cpu(), num_epoch=1, learning_rate=0.1)
model.fit(X, y, batch_size=32)
s = cm.compile_stats()
print(json.dumps({"cache_dir": cm.persistent_cache_dir(),
                  "compiles": s["compiles"],
                  "persistent_hits": s["persistent_cache_hits"],
                  "saved_s": s["persistent_cache_saved_seconds"]}))
"""


def test_persistent_cache_reused_across_subprocess(tmp_path):
    cache = str(tmp_path / "xla_cache")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": cache,
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}

    def run():
        r = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                           capture_output=True, text=True, cwd=REPO,
                           timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr
        return json.loads(r.stdout.strip().splitlines()[-1])

    in_checkout = os.path.join(REPO, ".jax_cache")
    listing = lambda: sorted(os.listdir(in_checkout)) \
        if os.path.isdir(in_checkout) else None  # noqa: E731
    before = listing()
    cold = run()
    assert cold["cache_dir"] == cache  # the resolver reports JAX's own dir
    assert listing() == before  # ...and nothing lands in the checkout's
    entries = [f for f in os.listdir(cache) if f.endswith("-cache")]
    assert entries, "cold run wrote nothing to the persistent cache"
    warm = run()
    # the warm process deserialized executables instead of compiling
    assert warm["persistent_hits"] > 0
    assert warm["persistent_hits"] >= cold["persistent_hits"]


def test_cache_resolver_env_wins_else_checkout(monkeypatch):
    """One rule for where the persistent cache lives: JAX's own
    JAX_COMPILATION_CACHE_DIR when set (and then nothing is set in code),
    else <checkout>/.jax_cache; MXNET_TPU_COMPILE_CACHE=0 turns the
    in-checkout cache off."""
    import jax

    from mxnet_tpu.utils import compile as cm

    updates = []
    real_update = jax.config.update

    def spy(key, value):
        updates.append(key)
        real_update(key, value)

    monkeypatch.setattr(jax.config, "update", spy)
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    # conftest's half of the rule: tier-1 runs with no cache directory, so
    # no compile-count assertion can depend on an earlier run
    assert cm.persistent_cache_dir() is None
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
    try:
        # set from outside: JAX owns it (it reads the variable at import —
        # the subprocess test below sees persistent_cache_dir() report it)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        cm.configure_persistent_cache()
        assert updates == []

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE", "0")
        assert cm.configure_persistent_cache() is None
        assert updates == []

        monkeypatch.delenv("MXNET_TPU_COMPILE_CACHE")
        in_checkout = os.path.join(REPO, ".jax_cache")
        assert cm.configure_persistent_cache() == in_checkout
        assert cm.persistent_cache_dir() == in_checkout
        assert "jax_compilation_cache_dir" in updates
    finally:
        real_update("jax_compilation_cache_dir", None)
        real_update("jax_persistent_cache_min_compile_time_secs", min_secs)


def test_masked_device_metrics_multi_position_labels():
    """(batch, T) labels ravel to batch*T rows inside device_update; the
    (batch,) validity mask must expand per position (regression: the mask
    broadcast against the flattened rows raised a shape error)."""
    import jax
    import jax.numpy as jnp

    # batch=2 rows x T=3 positions, flattened; row 2 is padding
    labels = jnp.asarray([0, 1, 2, 3, 3, 3], jnp.float32)
    preds = jax.nn.one_hot(jnp.asarray([0, 1, 0, 2, 2, 2]), 8,
                           dtype=jnp.float32) * 0.9 + 0.0125
    valid = jnp.asarray([1.0, 0.0])
    for name in ("accuracy", "perplexity", "ce", "top_k_accuracy"):
        masked = mx.metric.create(name)
        state = masked.device_update(masked.device_init(), [labels], [preds],
                                     valid=valid)
        masked.absorb_device_state(state)
        ref = mx.metric.create(name)
        state = ref.device_update(ref.device_init(), [labels[:3]],
                                  [preds[:3]])
        ref.absorb_device_state(state)
        assert abs(masked.get()[1] - ref.get()[1]) < 1e-5, name


# -- surfacing: profiler + monitor ---------------------------------------------

def test_profile_step_reports_compiles():
    import jax.numpy as jnp

    from mxnet_tpu.utils import profiler

    f = cm.tracked_jit(lambda x: jnp.tanh(x).sum(), label="unit:profiled")
    x = jnp.asarray(np.random.randn(32, 32).astype(np.float32))
    stats, log_dir, delta = profiler.profile_step(f, x, iters=2,
                                                  return_compile=True)
    assert os.path.isdir(log_dir)
    assert {"compiles", "compile_seconds", "hits", "misses"} <= set(delta)
    report = profiler.compile_report()
    assert "unit:profiled" in report


def test_monitor_collects_compile_stats():
    import jax.numpy as jnp

    mon = mx.Monitor(interval=1, track_compiles=True)
    rows = mon.collect_compiles()  # snapshot baseline
    f = cm.tracked_jit(lambda x: x * 3, label="unit:mon")
    f(jnp.ones((6,)))
    rows = mon.collect_compiles()
    by_name = {name: v for _, name, v in rows}
    assert by_name["compile/jit_misses"] >= 1
    assert any(name == "compile/unit:mon" for _, name, _ in rows)
    # a tracker wired to the monitor mirrors recompiles into its stat rows
    # (drained at the next collection, surviving toc()'s queue rebind)
    tr = cm.RecompileTracker(monitor=mon).arm()
    f(jnp.ones((9,)))
    tr.disarm()
    rows = mon.collect_compiles()
    assert any(str(name).startswith("recompile/unit:mon")
               for _, name, _ in rows)
    assert mon._recompile_events == []  # drained, not duplicated
