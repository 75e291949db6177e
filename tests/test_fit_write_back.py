"""``fit``'s epoch-end write-back: one batched device-to-host transfer of
every fully addressable leaf, landed on the host as NDArrays of the cpu
context. The reference every value is held to is the per-leaf
``_host_local`` loop over the same live state. CPU only: the "accelerator"
is a virtual CPU device other than the host's device 0, and the process's
default device is moved off device 0 the way a TPU process's is.
"""

import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import model as model_mod
from mxnet_tpu import telemetry
from mxnet_tpu.resilience import TrainingPreempted

ROWS, BATCH = 64, 16
STEPS = ROWS // BATCH

# name -> (ctx, kvstore): one device that is not the host's, and the
# four-device data-parallel mesh
PLACEMENTS = {
    "one_device": (lambda: mx.cpu(1), "local"),
    "dp4": (lambda: [mx.cpu(i) for i in range(4)], "device"),
}
placement = pytest.mark.parametrize("placement", sorted(PLACEMENTS))


def _net():
    net = mx.sym.Convolution(mx.sym.Variable("data"), num_filter=4,
                             kernel=(3, 3), name="c1")
    net = mx.sym.Activation(mx.sym.BatchNorm(net, name="bn1"),
                            act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net, name="flat"),
                                num_hidden=2, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _data(seed=5):
    rng = np.random.RandomState(seed)
    return (rng.randn(ROWS, 3, 8, 8).astype(np.float32),
            (rng.rand(ROWS) > 0.5).astype(np.float32))


class _Unaddressable:
    """A leaf as ``jax.distributed`` makes them, as far as the write-back
    looks: it says it is not fully addressable, and gives its rows through
    ``_host_local``'s ``np.asarray``."""

    is_fully_addressable = False

    def __init__(self, value):
        self.value = value
        self.nbytes = value.nbytes

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.value)


class _LiveState:
    """Wraps the train step ``fit`` builds, without changing what it
    dispatches, and keeps the last call's outputs: the live parameters
    (``[0]``) and auxiliary states (``[2]``) the next write-back reads.
    ``rewrite`` maps a call's ordinal to a function of (params, aux) that
    returns the two dicts to hand back in their place."""

    def __init__(self, model, rewrite=None):
        self.calls = 0
        self.params = self.aux = None
        rewrite = rewrite or {}
        build = model._build_train_step

        def spy_build(*args, **kwargs):
            run = build(*args, **kwargs)

            def spied(*step_args):
                out = run(*step_args)
                self.calls += 1
                params, aux = out[0], out[2]
                if self.calls in rewrite:
                    params, aux = rewrite[self.calls](dict(params), dict(aux))
                    out = (params, out[1], aux) + tuple(out[3:])
                self.params, self.aux = params, aux
                return out

            spied.__dict__.update(run.__dict__)
            return spied

        model._build_train_step = spy_build


def _model(placement, num_epoch):
    ctx, kvstore = PLACEMENTS[placement]
    model = mx.FeedForward(_net(), ctx=ctx(), num_epoch=num_epoch,
                           initializer=mx.init.Xavier(), learning_rate=0.1,
                           momentum=0.9)
    return model, kvstore


def _fit(placement, num_epoch=2, rewrite=None, callback=None, **fit_args):
    """``fit`` in a process whose default device is not the host's device
    0 (on a TPU host it is chip 0). Returns the model and the spy."""
    telemetry.reset()
    X, y = _data()
    model, kvstore = _model(placement, num_epoch)
    live = _LiveState(model, rewrite)
    with jax.default_device(jax.devices()[2]):
        model.fit(X, y, batch_size=BATCH, kvstore=kvstore,
                  epoch_end_callback=callback, **fit_args)
    return model, live


def _written_back(model):
    return {**model.arg_params, **model.aux_params}


def _per_leaf_reference(live):
    """What the per-leaf loop gives for the live state."""
    return {k: model_mod._host_local(v)
            for k, v in {**live.params, **live.aux}.items()}


def _spans():
    return [r["attrs"] for r in telemetry.span_records()
            if r["name"] == "fit.epoch.write_back"]


@placement
def test_values_are_bitwise_the_per_leaf_loops_with_dtype_and_shape(
        placement):
    def mixed(params, aux):
        # a bf16 and an integer leaf in the state the last write-back reads
        params["fc_weight"] = params["fc_weight"].astype(jnp.bfloat16)
        aux["bn1_moving_var"] = (aux["bn1_moving_var"] * 1000).astype(
            jnp.int32)
        return params, aux

    model, live = _fit(placement, num_epoch=1, rewrite={STEPS: mixed})
    assert live.calls == STEPS
    want = _per_leaf_reference(live)
    got = _written_back(model)
    assert sorted(got) == sorted(want) and len(got) == 8
    for k, ref in want.items():
        value = got[k].asnumpy()
        assert got[k].dtype == ref.dtype and value.dtype == ref.dtype, k
        assert got[k].shape == ref.shape, k
        assert value.tobytes() == ref.tobytes(), k
    assert got["fc_weight"].dtype == jnp.bfloat16
    assert got["bn1_moving_var"].dtype == np.int32
    assert got["bn1_moving_var"].asnumpy().any()


@placement
def test_arrays_a_callback_kept_hold_their_epochs_values_after_later_steps(
        placement):
    kept, then = [], []

    def keep(epoch, symbol, arg_params, aux_params):
        arrays = {**arg_params, **aux_params}
        kept.append(arrays)
        then.append({k: v.asnumpy().tobytes() for k, v in arrays.items()})

    model, live = _fit(placement, num_epoch=3, callback=keep)
    assert live.calls == 3 * STEPS and len(kept) == 3
    for arrays, snapshot in zip(kept, then):
        assert {k: v.asnumpy().tobytes()
                for k, v in arrays.items()} == snapshot
    # the donated steps in between did move the state
    assert then[0]["fc_weight"] != then[1]["fc_weight"] != \
        then[2]["fc_weight"]
    assert then[2] == {k: v.asnumpy().tobytes()
                       for k, v in _written_back(model).items()}


@placement
def test_every_array_handed_over_is_on_the_cpu_context(placement):
    seen = []

    def contexts(epoch, symbol, arg_params, aux_params):
        seen.append({v.context for v in
                     list(arg_params.values()) + list(aux_params.values())})

    model, live = _fit(placement, callback=contexts)
    assert seen == [{mx.cpu(0)}] * 2
    host = mx.cpu(0).jax_device
    for k, v in _written_back(model).items():
        assert v.context == mx.cpu(0) and v.data.devices() == {host}, k
    # the live state was elsewhere (with dp4: on devices 1-3 too)
    assert any(v.devices() != {host} for v in live.params.values())
    # and what was handed over can be served from ctx as it is
    X, _ = _data()
    out = model.predict(X[:BATCH], batch_size=BATCH)
    assert out.shape == (BATCH, 2) and np.isfinite(out).all()


@placement
def test_no_per_leaf_copy_of_a_fully_addressable_leaf(placement,
                                                      monkeypatch):
    calls = []
    per_leaf = model_mod._host_local
    monkeypatch.setattr(model_mod, "_host_local",
                        lambda x: calls.append(x) or per_leaf(x))
    _fit(placement)
    # nothing else in this fit (no guards, no eval data, device metric)
    # reads through _host_local, so the write-back's calls are all of them
    assert calls == [] and len(_spans()) == 2


def test_a_leaf_that_is_not_fully_addressable_goes_by_itself(monkeypatch):
    stubs = []

    def one_stub(params, aux):
        stubs.append(_Unaddressable(params["fc_weight"]))
        params["fc_weight"] = stubs[0]
        return params, aux

    calls = []
    per_leaf = model_mod._host_local
    monkeypatch.setattr(model_mod, "_host_local",
                        lambda x: calls.append(x) or per_leaf(x))
    model, live = _fit("one_device", num_epoch=1, rewrite={STEPS: one_stub})
    assert calls == stubs and len(stubs) == 1
    (attrs,) = _spans()
    assert (attrs["arrays"], attrs["batched"]) == (8, 7)
    assert attrs["bytes"] == sum(v.nbytes for v in
                                 {**live.params, **live.aux}.values())
    got = _written_back(model)
    assert got["fc_weight"].asnumpy().tobytes() == \
        np.asarray(stubs[0].value).tobytes()
    assert got["fc_weight"].context == mx.cpu(0)
    assert got["fc_bias"].asnumpy().tobytes() == \
        np.asarray(live.params["fc_bias"]).tobytes()


@placement
def test_write_back_span_counts_arrays_bytes_and_batched(placement):
    model, _ = _fit(placement)
    got = _written_back(model)
    nbytes = sum(v.asnumpy().nbytes for v in got.values())
    assert _spans() == [
        {"epoch": e, "arrays": len(got), "bytes": nbytes,
         "batched": len(got)} for e in range(2)]


def test_preempt_flush_leaves_the_current_values(tmp_path):
    at_callback = []

    def keep(epoch, symbol, arg_params, aux_params):
        at_callback.append(arg_params["fc_weight"].asnumpy().tobytes())

    def sigterm_at(param):
        if param.epoch == 1 and param.nbatch == 2:
            signal.raise_signal(signal.SIGTERM)

    telemetry.reset()
    X, y = _data()
    model, kvstore = _model("one_device", num_epoch=3)
    live = _LiveState(model)
    with pytest.raises(TrainingPreempted) as stopped:
        model.fit(X, y, batch_size=BATCH, kvstore=kvstore,
                  epoch_end_callback=keep, batch_end_callback=sigterm_at,
                  sharded_checkpoint_dir=str(tmp_path / "ckpt"))
    assert stopped.value.epoch == 1 and len(at_callback) == 1
    assert STEPS < live.calls < 2 * STEPS       # stopped inside epoch 1
    want = _per_leaf_reference(live)
    got = _written_back(model)
    for k, ref in want.items():
        assert got[k].asnumpy().tobytes() == ref.tobytes(), k
        assert got[k].context == mx.cpu(0), k
    assert got["fc_weight"].asnumpy().tobytes() != at_callback[0]
    # the epoch's write-back, then the flush's: the same span, both batched
    assert [(a["epoch"], a["batched"] == a["arrays"])
            for a in _spans()] == [(0, True), (1, True)]


def _net_with_a_padded_leaf():
    # fc1_weight is (64, 144): its last axis is not a whole number of
    # 128-lane tiles and its 9,216 values are nine rows of 1,024
    net = mx.sym.Convolution(mx.sym.Variable("data"), num_filter=4,
                             kernel=(3, 3), name="c1")
    net = mx.sym.Flatten(mx.sym.Activation(net, act_type="relu",
                                           name="relu1"), name="flat")
    net = mx.sym.FullyConnected(net, num_hidden=64, name="fc1")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_which_leaves_land_as_dense_rows(monkeypatch):
    monkeypatch.setattr(model_mod, "_DENSE_MIN_BYTES", 0)
    lands = model_mod._lands_padded
    assert lands(jnp.zeros((64, 144)))
    assert lands(jnp.zeros((8, 16, 232), jnp.bfloat16))
    assert not lands(jnp.zeros((64, 256)))        # whole lane tiles
    assert not lands(jnp.zeros((3, 100)))         # no whole rows of 1,024
    assert not lands(jnp.zeros(()))
    assert not lands(np.zeros((64, 144), np.float32))
    monkeypatch.undo()
    assert not lands(jnp.zeros((64, 144)))        # under 16 MB


@placement
def test_a_padded_leaf_lands_as_dense_rows_with_the_same_bytes(
        placement, monkeypatch):
    monkeypatch.setattr(model_mod, "_DENSE_MIN_BYTES", 0)
    calls = []
    as_rows = model_mod._as_rows
    monkeypatch.setattr(model_mod, "_as_rows",
                        lambda x: calls.append(x.shape) or as_rows(x))
    telemetry.reset()
    X, y = _data()
    ctx, kvstore = PLACEMENTS[placement]
    model = mx.FeedForward(_net_with_a_padded_leaf(), ctx=ctx(),
                           num_epoch=2, initializer=mx.init.Xavier(),
                           learning_rate=0.1, momentum=0.9)
    live = _LiveState(model)
    with jax.default_device(jax.devices()[2]):
        model.fit(X, y, batch_size=BATCH, kvstore=kvstore)
    assert calls == [(64, 144)] * 2               # once an epoch
    want = _per_leaf_reference(live)
    got = _written_back(model)
    assert sorted(got) == sorted(want)
    for k, ref in want.items():
        value = got[k].asnumpy()
        assert value.dtype == ref.dtype and value.shape == ref.shape, k
        assert value.tobytes() == ref.tobytes(), k
        assert got[k].context == mx.cpu(0), k
    (first, second) = _spans()
    assert first["batched"] == first["arrays"] == len(got)


def _net_with_a_stored_padded_leaf():
    # the expert layer keeps gate_weight and up_weight on the device as
    # (4, 16, 48), the order its grouped product reads (declared
    # (4, 48, 16)); down_weight is (4, 16, 48) as declared. All three have
    # a last axis of part of a lane tile and 3,072 values: three rows (and
    # so has fc1_weight, (16, 192), as declared)
    net = mx.sym.Flatten(mx.sym.Variable("data"), name="flat")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = mx.sym.MixtureOfExperts(data=net, num_experts=4, experts_held=4,
                                  top_k=2, expert_width=48, name="moe")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


@placement
def test_a_stored_padded_leaf_lands_in_its_declared_order_from_the_device(
        placement, monkeypatch):
    """A lane-padded leaf kept in a stored order is taken back to its
    declared axes in the copy that makes its dense rows, and the host
    transposes nothing: the values are bitwise the live state's, taken
    back to the declared order."""
    monkeypatch.setattr(model_mod, "_DENSE_MIN_BYTES", 0)
    calls = []
    as_rows = model_mod._as_rows
    monkeypatch.setattr(
        model_mod, "_as_rows",
        lambda x, **kw: calls.append((x.shape, kw)) or as_rows(x, **kw))
    reorders = []
    reorder = model_mod._reorder
    monkeypatch.setattr(
        model_mod, "_reorder",
        lambda tree, order, back=False: reorders.append((back, order))
        or reorder(tree, order, back))
    telemetry.reset()
    X, y = _data()
    ctx, kvstore = PLACEMENTS[placement]
    model = mx.FeedForward(_net_with_a_stored_padded_leaf(), ctx=ctx(),
                           num_epoch=2, initializer=mx.init.Xavier(),
                           learning_rate=0.1, momentum=0.9)
    live = _LiveState(model)
    with jax.default_device(jax.devices()[2]):
        model.fit(X, y, batch_size=BATCH, kvstore=kvstore)
    stored = {"moe_gate_weight", "moe_up_weight"}
    assert model._state_order() == dict.fromkeys(stored, (0, 2, 1))
    epoch = [((16, 192), {}), ((4, 16, 48), {}),
             ((4, 16, 48), {"axes": (0, 2, 1)}),
             ((4, 16, 48), {"axes": (0, 2, 1)})]
    assert sorted(calls, key=repr) == sorted(epoch * 2, key=repr)
    # the write-backs' host reorders are empty
    assert [order for back, order in reorders if back] == [{}, {}]
    want = {k: model_mod._host_local(v)
            for k, v in {**model_mod._reorder(
                live.params, model._state_order(), back=True),
                **live.aux}.items()}
    got = _written_back(model)
    assert sorted(got) == sorted(want)
    for k, ref in want.items():
        value = got[k].asnumpy()
        assert value.dtype == ref.dtype and value.shape == ref.shape, k
        assert value.tobytes() == ref.tobytes(), k
        assert got[k].context == mx.cpu(0), k
    assert got["moe_up_weight"].shape == (4, 48, 16)
