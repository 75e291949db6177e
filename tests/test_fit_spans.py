"""The spans inside ``FeedForward.fit`` (``telemetry.phase()``): which are
recorded, how they nest, what their attributes count, that they land in a
``jax.profiler`` trace, and that they change nothing the step computes.
CPU only; no assertion compares host-clock times.
"""

import collections
import glob
import os
import threading

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry.hub import SPAN_RING

ROWS, BATCH, EPOCHS = 48, 16, 2
STEPS = ROWS // BATCH
EPOCH_SPANS = ["fit.epoch.feed_start", "fit.epoch.feed_close",
               "fit.epoch.drain", "fit.epoch.metric_pull",
               "fit.epoch.checkpoint", "fit.epoch.write_back",
               "fit.epoch.eval", "fit.epoch.callback"]


def _net():
    """A convolution, BatchNorm (two auxiliary states) and dropout (so the
    step draws a key), then a two-way head."""
    net = mx.sym.Convolution(mx.sym.Variable("data"), num_filter=4,
                             kernel=(3, 3), name="c1")
    net = mx.sym.Activation(mx.sym.BatchNorm(net, name="bn1"),
                            act_type="relu", name="relu1")
    net = mx.sym.Dropout(net, p=0.3, name="drop1")
    # every node named: the program's fingerprint is the same each build
    net = mx.sym.FullyConnected(mx.sym.Flatten(net, name="flat"),
                                num_hidden=2, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _data(seed=3):
    rng = np.random.RandomState(seed)
    return (rng.randn(ROWS, 3, 8, 8).astype(np.float32),
            (rng.rand(ROWS) > 0.5).astype(np.float32))


def _fit(ckpt_dir=None, with_eval=True, callback=None, seed=11):
    """One ``fit`` of ``EPOCHS`` epochs from fixed seeds: the model, what
    the epoch callback saw of the parameters, and the train program's rows
    of ``compile_stats()``."""
    mx.random.seed(seed)
    np.random.seed(seed)
    mx.utils.reset_compile_stats()
    X, y = _data()
    seen = []

    def snapshot(epoch, symbol, arg_params, aux_params):
        seen.append({k: v.asnumpy().tobytes()
                     for k, v in sorted({**arg_params,
                                         **aux_params}.items())})
        if callback is not None:
            callback(epoch)

    metric = mx.metric.CrossEntropy()
    model = mx.FeedForward(_net(), ctx=mx.cpu(), num_epoch=EPOCHS,
                           initializer=mx.init.Xavier(), learning_rate=0.1,
                           momentum=0.9)
    model.fit(X, y, batch_size=BATCH, eval_metric=metric,
              eval_data=(X[:BATCH], y[:BATCH]) if with_eval else None,
              epoch_end_callback=snapshot,
              sharded_checkpoint_dir=ckpt_dir)
    programs = {
        label: {k: v for k, v in row.items() if "seconds" not in k}
        for label, row in mx.utils.compile_stats()["per_function"].items()
        if label.startswith("train_step:")}
    return model, seen, float(metric.get()[1]), programs


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One fit with every ``fit.epoch.*`` span applying, and its records."""
    telemetry.reset()
    model, seen, _, _ = _fit(ckpt_dir=str(tmp_path_factory.mktemp("ckpt")))
    return {"model": model, "seen": seen,
            "records": telemetry.span_records(),
            "dropped": telemetry.spans_dropped()}


def _main(records):
    return sorted((r for r in records if r["thread"] != "mx-prefetch"),
                  key=lambda r: r["start"])


def test_epoch_spans_once_each_in_order_under_fit_epoch(run):
    assert run["dropped"] == 0
    main = _main(run["records"])
    epochs = [r for r in main if r["name"] == "fit.epoch"]
    assert [r["epoch"] for r in epochs] == list(range(EPOCHS))
    for epoch in epochs:
        assert epoch["parent"] is None
        own = [r for r in main if r["name"].startswith("fit.epoch.")
               and r["epoch"] == epoch["epoch"]]
        assert [r["name"] for r in own] == EPOCH_SPANS
        for r in own:
            assert r["parent"] == "fit.epoch" and r["step"] is None
            assert epoch["start"] <= r["start"] <= r["end"] <= epoch["end"]
            assert r["attrs"]["epoch"] == epoch["epoch"]
        for a, b in zip(own, own[1:]):      # one after the other
            assert a["end"] <= b["start"]


def test_each_step_has_its_feed_wait_then_a_step_with_two_children(run):
    main = _main(run["records"])
    for epoch in range(EPOCHS):
        own = [r for r in main if r["epoch"] == epoch and r["name"] in (
            "fit.feed_wait", "fit.step", "fit.dispatch", "fit.step_host")]
        # the last feed_wait of an epoch waits for the feed's end
        assert [(r["name"], r["step"]) for r in own] == [
            (name, step) for step in range(STEPS) for name in (
                "fit.feed_wait", "fit.step", "fit.dispatch",
                "fit.step_host")] + [("fit.feed_wait", STEPS)]
        by = {(r["name"], r["step"]): r for r in own}
        for step in range(STEPS):
            wait, whole = by["fit.feed_wait", step], by["fit.step", step]
            first, second = by["fit.dispatch", step], \
                by["fit.step_host", step]
            assert wait["parent"] == whole["parent"] == "fit.epoch"
            assert first["parent"] == second["parent"] == "fit.step"
            assert wait["end"] <= whole["start"] <= first["start"]
            assert first["end"] <= second["start"]
            assert second["end"] <= whole["end"]


def test_feed_thread_spans_carry_its_name_epoch_and_batch_ordinal(run):
    feed = [r for r in run["records"] if r["name"].startswith("feed.")]
    assert {r["thread"] for r in feed} == {"mx-prefetch"}
    assert not [r for r in run["records"]
                if r["thread"] == "mx-prefetch" and r not in feed]
    counts = collections.Counter((r["name"], r["epoch"]) for r in feed)
    for epoch in range(EPOCHS):
        # one more next(): the one that finds the iterator exhausted
        assert counts["feed.produce", epoch] == STEPS + 1
        assert counts["feed.place", epoch] == STEPS
        assert counts["feed.queue_full", epoch] == STEPS
        places = sorted((r for r in feed if r["name"] == "feed.place"
                         and r["epoch"] == epoch), key=lambda r: r["start"])
        assert [r["step"] for r in places] == list(range(STEPS))
    assert all(r["parent"] is None for r in feed)


def test_write_back_counts_parameters_and_auxiliary_states(run):
    model = run["model"]
    arrays = len(model.arg_params) + len(model.aux_params)
    nbytes = sum(v.asnumpy().nbytes for v in
                 list(model.arg_params.values())
                 + list(model.aux_params.values()))
    assert len(model.aux_params) == 2
    spans = [r for r in run["records"]
             if r["name"] == "fit.epoch.write_back"]
    assert len(spans) == EPOCHS
    for r in spans:
        assert r["attrs"]["arrays"] == arrays
        assert r["attrs"]["bytes"] == nbytes
        # every leaf went through the one batched transfer
        assert r["attrs"]["batched"] == arrays


def test_feed_place_counts_the_host_bytes_of_a_numpy_batch(run):
    X, y = _data()
    batch_bytes = X[:BATCH].nbytes + y[:BATCH].nbytes
    places = [r for r in run["records"] if r["name"] == "feed.place"]
    assert places and {r["attrs"]["bytes"] for r in places} == {batch_bytes}


def test_set_up_spans(run):
    by = collections.defaultdict(list)
    for r in run["records"]:
        by[r["name"]].append(r)
    (start,) = by["fit.start"]
    (init,) = by["setup.init_params"]
    (place,) = by["setup.place_state"]
    assert init["parent"] == place["parent"] == "fit.start"
    model = run["model"]
    assert init["attrs"]["arrays"] == len(model.arg_params) \
        + len(model.aux_params)
    first_epoch = min(r["start"] for r in by["fit.epoch"])
    assert start["start"] <= init["start"] <= place["end"] <= start["end"] \
        <= first_epoch
    # precompile: its own init_params (fit's then finds nothing to do) and
    # one setup.compile a program, labelled like compile_stats()
    telemetry.reset()
    X, y = _data()
    model = mx.FeedForward(_net(), ctx=mx.cpu(), num_epoch=1,
                           initializer=mx.init.Xavier(), learning_rate=0.1)
    data = mx.io.NDArrayIter(X, y, batch_size=BATCH)
    warm = model.precompile(data=data, eval_metric="ce")
    model.fit(data, eval_metric="ce")
    records = telemetry.span_records()
    compiles = [r for r in records if r["name"] == "setup.compile"]
    assert [r["attrs"]["label"] for r in compiles] == warm["labels"]
    inits = [r["attrs"]["arrays"] for r in records
             if r["name"] == "setup.init_params"]
    assert inits == [init["attrs"]["arrays"], 0]


def test_ring_drops_oldest_first_and_counts_it():
    telemetry.reset()
    extra = 5
    for i in range(SPAN_RING + extra):
        with telemetry.phase("ring.fill", step=i):
            pass
    records = telemetry.span_records()
    assert len(records) == SPAN_RING
    assert telemetry.spans_dropped() == extra
    assert [records[0]["step"], records[-1]["step"]] == [
        extra, SPAN_RING + extra - 1]
    cut = records[-3]["end"]
    assert [r["step"] for r in telemetry.span_records(since=cut)] == [
        r["step"] for r in records[-3:]]
    telemetry.reset()       # the ring lives with the hub
    assert telemetry.span_records() == [] and telemetry.spans_dropped() == 0


def test_parent_epoch_and_step_come_from_the_enclosing_span():
    telemetry.reset()
    seen = {}

    def other_thread():
        with telemetry.phase("elsewhere"):
            pass
        seen["done"] = True

    with telemetry.phase("outer", epoch=4, step=2, note="x") as outer:
        with telemetry.phase("inner") as inner:
            inner.attrs["bytes"] = 7
            worker = threading.Thread(target=other_thread, name="side")
            worker.start()
            worker.join(timeout=30)
        outer.end()
        outer.end()                      # a second close is a no-op
    assert seen == {"done": True}
    by = {r["name"]: r for r in telemetry.span_records()}
    assert len(telemetry.span_records()) == 3
    assert by["inner"]["parent"] == "outer"
    assert (by["inner"]["epoch"], by["inner"]["step"]) == (4, 2)
    assert by["inner"]["attrs"] == {"bytes": 7}
    assert by["outer"]["attrs"] == {"epoch": 4, "step": 2, "note": "x"}
    assert by["elsewhere"]["parent"] is None         # a stack a thread
    assert by["elsewhere"]["thread"] == "side"
    hists = telemetry.hub().snapshot()["histograms"]
    assert hists["inner_seconds"]["count"] == 1


def test_span_closed_by_an_exception_from_the_epoch_callback_is_recorded():
    class Stop(Exception):
        pass

    def stop(epoch):
        raise Stop

    telemetry.reset()
    with pytest.raises(Stop):
        _fit(with_eval=False, callback=stop)
    by = collections.Counter(r["name"] for r in telemetry.span_records())
    assert by["fit.epoch.callback"] == by["fit.epoch"] == 1
    assert by["fit.start"] == 1 and by["fit.step"] == STEPS
    # nothing stays open on the thread: the next span has no parent
    with telemetry.phase("after"):
        pass
    assert telemetry.span_records()[-1]["parent"] is None


class _NoSpan:
    """What ``fit`` needs of a span, recording nothing: the loop as it was
    before it had spans."""

    def __init__(self, name, **attrs):
        self.attrs = attrs
        self.start = self.end_ts = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def end(self):
        pass


def test_spans_change_no_arithmetic_and_no_program(monkeypatch):
    telemetry.reset()
    _, seen, loss, programs = _fit(with_eval=False)
    assert telemetry.span_records()
    telemetry.reset()
    monkeypatch.setattr(telemetry, "phase", _NoSpan)
    _, bare_seen, bare_loss, bare_programs = _fit(with_eval=False)
    # no span; what the ring holds is the compile registry's own records
    assert {r["name"] for r in telemetry.span_records()} == {
        "compile.backend"}
    assert len(seen) == EPOCHS and seen == bare_seen     # bitwise
    assert loss == bare_loss
    # one train program, the same fingerprint, the same counts
    assert programs == bare_programs and len(programs) == 1
    (row,) = programs.values()
    assert row["programs"] == 1 and row["compiles"] == 1
    assert row["misses"] == 1 and row["hits"] == EPOCHS * STEPS - 1


def test_spans_are_in_the_profilers_trace_inside_their_parent(tmp_path):
    telemetry.reset()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _fit(with_eval=False)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    lines = [line for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines]
    parent_of = {r["name"]: r["parent"] for r in telemetry.span_records()}
    for name, per_epoch in (("fit.epoch.write_back", 1),
                            ("fit.dispatch", STEPS)):
        # the in-memory records name the chain up to fit.epoch; the trace's
        # events have to nest the same way, on one host thread's line
        chain = [parent_of[name]]
        while chain[-1] != "fit.epoch":
            chain.append(parent_of[chain[-1]])
        found = 0
        for line in lines:
            events = list(line.events)
            for e in events:
                if e.name != "mx." + name:
                    continue
                found += 1
                assert dict(e.stats)["epoch"] in range(EPOCHS)
                for ancestor in chain:
                    assert any(
                        o.start_ns <= e.start_ns and e.start_ns
                        + e.duration_ns <= o.start_ns + o.duration_ns
                        for o in events if o.name == "mx." + ancestor), \
                        (name, ancestor)
        assert found == EPOCHS * per_epoch
