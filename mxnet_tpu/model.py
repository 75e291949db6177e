"""FeedForward: the estimator-style trainer (reference: python/mxnet/model.py).

API parity: ``FeedForward(symbol, ctx, num_epoch, optimizer, initializer,
...)`` with ``fit / predict / score / save / load / create`` and the
checkpoint format `prefix-symbol.json` + `prefix-%04d.params`.

TPU-native execution (this is where the reference and this framework differ
most — reference call stack in SURVEY.md §3.1):

  reference: per-device GraphExecutors + engine-pushed op graph per batch +
             kvstore push/pull per parameter + python-side SGD NDArray ops.
  here:      ONE jitted train step per (shapes, dtype): forward + backward
             (jax.grad) + optimizer update fused into a single XLA program
             with donated parameter/optimizer buffers. Multi-device data
             parallelism is a `jax.sharding.Mesh` over the given ctx list
             with the batch sharded on the 'dp' axis — the SPMD partitioner
             inserts the gradient psum over ICI (≙ kvstore 'device'
             allreduce, kvstore_device.h) and overlaps it with backward
             compute (≙ priority-ordered push/pull, model.py:319-325).

  The kvstore argument keeps its reference meaning as a *strategy selector*:
  None/'local'/'device' single-process; 'dist_sync' extends the mesh across
  processes (multi-host). 'update_on_kvstore' semantics (weights updated
  once, then broadcast) equal 'local' updates under BSP, so both collapse to
  the same fused step; see SURVEY.md §2.4 hard-part #2.

  Mixed precision: ``compute_dtype=jnp.bfloat16`` keeps master params in f32
  and runs compute in bf16 (the reference is f32-only; dtype policy per
  SURVEY.md hard-part #7).
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import logging
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import initializer as init_mod
from . import io as io_mod
from . import kvstore as kvstore_mod
from . import metric as metric_mod
from . import ndarray as nd
from . import optimizer as opt_mod
from . import random as random_mod
from . import symbol as sym_mod
from . import telemetry as telemetry_mod
from .resilience import chaos as chaos_mod
from .resilience import guards as guards_mod
from .resilience import preempt as preempt_mod
from .utils import compile as compile_mod
from .base import MXNetError
from .callback import BatchEndParam
from .context import Context, cpu, current_context
from .executor import _build_graph_fn
from .ndarray import NDArray, array
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["FeedForward", "save_checkpoint", "load_checkpoint"]

BASE_ESTIMATOR = object


def save_checkpoint(prefix, epoch, symbol, arg_params, aux_params):
    """Write `prefix-symbol.json` + `prefix-%04d.params` (reference:
    model.py:392-421).

    Both files go through the sharded tier's atomic writer (ISSUE 17:
    tmp + ``os.replace`` + a ``.crc32`` sidecar), so a kill mid-save can
    no longer tear the params file — the old file stays whole until the
    new one is fully on disk."""
    from .utils import checkpoint as ckpt_mod

    ckpt_mod.atomic_write(f"{prefix}-symbol.json",
                          lambda tmp: symbol.save(tmp))
    save_dict = {f"arg:{k}": v for k, v in arg_params.items()}
    save_dict.update({f"aux:{k}": v for k, v in aux_params.items()})
    ckpt_mod.atomic_write(f"{prefix}-{epoch:04d}.params",
                          lambda tmp: nd.save(tmp, save_dict))
    logging.info("Saved checkpoint to \"%s-%04d.params\"", prefix, epoch)


def load_checkpoint(prefix, epoch):
    """Load what save_checkpoint wrote; returns (symbol, arg_params, aux_params)
    (reference: model.py:452-461). Files written by the atomic path carry
    a ``.crc32`` sidecar that is verified here — a torn or corrupt params
    file fails loud instead of loading garbage; pre-sidecar legacy files
    load as before."""
    from .utils import checkpoint as ckpt_mod

    params_path = f"{prefix}-{epoch:04d}.params"
    if ckpt_mod.check_sidecar(params_path) is False:
        raise MXNetError(
            f"checkpoint {params_path} fails its CRC sidecar "
            "(torn or corrupt write) — refusing to load")
    symbol = sym_mod.load(f"{prefix}-symbol.json")
    save_dict = nd.load(params_path)
    arg_params, aux_params = {}, {}
    for k, v in save_dict.items():
        tp, name = k.split(":", 1)
        if tp == "arg":
            arg_params[name] = v
        elif tp == "aux":
            aux_params[name] = v
    return symbol, arg_params, aux_params


def _as_list(x):
    return x if isinstance(x, list) else [x]


def _init_iter(X, y, batch_size, shuffle=False, is_train=True):
    """Coerce numpy/NDArray input into an iterator (reference: _init_iter)."""
    if isinstance(X, io_mod.DataIter):
        return X
    if isinstance(X, (np.ndarray, NDArray)):
        if is_train and y is None:
            raise MXNetError("y is required when X is array-like")
        # reference model.py:609 clamps batch_size to the dataset size
        batch_size = min(batch_size, X.shape[0])
        return io_mod.NDArrayIter(X, y, batch_size=batch_size, shuffle=shuffle)
    raise MXNetError(f"cannot handle input type {type(X)}")


def _host_local(x):
    """A jax.Array (possibly spanning non-addressable devices under
    jax.distributed) -> this process's local numpy view.

    Replicated arrays -> the single local copy; batch-sharded arrays -> the
    concatenation of this process's shards (its own rows of the global
    batch). Reference analog: workers only ever observe their own slice
    (model.py:244-246 _split_input_slice)."""
    if not isinstance(x, jax.Array) or x.is_fully_addressable:
        return np.asarray(x)
    uniq = {}
    for s in x.addressable_shards:
        key = tuple((sl.start, sl.stop) for sl in s.index)
        uniq.setdefault(key, s)
    shards = sorted(uniq.values(),
                    key=lambda s: tuple(sl.start or 0 for sl in s.index))
    if len(shards) == 1:
        return np.asarray(shards[0].data)
    return np.concatenate([np.asarray(s.data) for s in shards], axis=0)


def _to_dev(x, dev):
    """Move an array to `dev` unless it already lives there COMMITTED
    (committed host arrays from data iterators must not pin jit to the cpu
    backend). Uncommitted arrays are committed in place even when already
    on `dev`: the jit cache keys on placement, and a mix of committed and
    uncommitted calls for the same shapes compiles the program twice."""
    try:
        if isinstance(x, jax.Array) and x.devices() == {dev} \
                and getattr(x, "_committed", True):
            return x
    except Exception:  # pragma: no cover - non-Array leaves
        pass
    return jax.device_put(x, dev)


def _place(value, sharding):
    """Place host data onto a (possibly multi-process) mesh sharding.

    Under jax.distributed a plain device_put cannot target non-addressable
    devices; each process contributes its local value as its part of the
    global array instead (its batch shard, or its replica copy).

    Values that are ALREADY global jax.Arrays (the async feed pre-places
    batches) pass through: np.asarray on an array spanning non-addressable
    devices raises, and the re-place would be wasted work anyway."""
    if isinstance(value, jax.Array):
        try:
            if value.sharding.is_equivalent_to(sharding, value.ndim):
                return value
        except Exception:  # pragma: no cover - defensive; differing mesh objs
            pass
        if not value.is_fully_addressable:
            # global array under a different sharding: reshard on device —
            # fetching to host across processes is impossible by definition
            return jax.device_put(value, sharding)
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(sharding,
                                                      np.asarray(value))
    return jax.device_put(value, sharding)


class _AsyncDeviceFeed:
    """Double-buffered feed/compute overlap for the train loop.

    A background thread draws batches from the (already host-prefetching)
    iterator and immediately starts their async host->device transfer, so
    by the time the train loop needs batch N+1, both its host assembly and
    its wire/PCIe transfer have been hiding under the device's step N.
    Without this, the transfer only starts after step N is *dispatched*,
    and an io-fed epoch costs feed + compute instead of max(feed, compute)
    (reference overlapped IO the same way by construction:
    src/io/iter_prefetcher.h:34-126 — a ThreadedIter in front of the
    consumer; here the device transfer itself is part of the hidden work).

    ``depth`` bounds in-flight batches (2 = classic double buffering) so a
    fast iterator cannot queue an epoch of device buffers. Iterator
    exceptions surface in the consuming thread. Disable with
    MXTPU_FEED_PREFETCH=0 (the fit loop then feeds synchronously).

    Buffer-reuse contract: the feed runs up to ``depth`` batches ahead, and
    device_put may read the host buffers asynchronously, so iterators feeding
    fit must hand over FRESH data arrays per batch (every in-repo iterator
    does; an iterator recycling one buffer, reference ThreadedIter-style,
    would corrupt in-flight transfers). Labels are defensively copied by
    ``snapshot`` in fit — they are retained far longer (until the metric
    update after the step completes) than the data transfer window.
    """

    _SENTINEL = object()

    def __init__(self, data_iter, extract, place, depth=2, snapshot=None,
                 epoch=None):
        import queue
        import threading

        self._q = queue.Queue(maxsize=max(1, int(depth)))
        self._err = None
        self._closed = False

        def worker():
            # spans of the feed thread (attrs: the epoch being fed and the
            # batch's ordinal in it): feed.produce is the iterator's next(),
            # feed.place the extraction and the dispatch of the transfer,
            # feed.queue_full the put, which blocks while the consumer has
            # ``depth`` batches in hand: the healthy state
            try:
                batches = iter(data_iter)
                for step in itertools.count():
                    with telemetry_mod.phase("feed.produce", epoch=epoch,
                                             step=step):
                        try:
                            batch = next(batches)
                        except StopIteration:
                            break
                    with telemetry_mod.phase("feed.place", epoch=epoch,
                                             step=step) as span:
                        arrays = extract(batch)
                        span.attrs["bytes"] = _host_nbytes(arrays)
                        # place() dispatches the async device_put; the
                        # consumer gets arrays whose transfer is in flight
                        placed = place(arrays)
                    if snapshot is not None:
                        batch = snapshot(batch)
                    item = (batch, placed)
                    with telemetry_mod.phase("feed.queue_full", epoch=epoch,
                                             step=step):
                        while not self._closed:
                            try:
                                self._q.put(item, timeout=0.2)
                                break
                            except queue.Full:
                                continue
                    if self._closed:
                        return
            except BaseException as e:  # noqa: BLE001 - re-raised on main
                self._err = e
            finally:
                # the SENTINEL must not be droppable: with the queue full
                # (feed faster than compute — the steady state) a single
                # bounded put could time out and leave the consumer blocked
                # in q.get() forever, so retry until delivered or closed
                while not self._closed:
                    try:
                        self._q.put(self._SENTINEL, timeout=0.2)
                        break
                    except queue.Full:
                        continue

        self._thread = threading.Thread(
            target=worker, daemon=True, name="mx-prefetch")
        self._thread.start()

    def close(self):
        """Stop the worker and release the iterator (so a caller that hits
        an exception mid-epoch can reset() the iterator without racing the
        still-feeding thread)."""
        self._closed = True
        while not self._q.empty():
            try:
                self._q.get_nowait()
            except Exception:  # pragma: no cover - drained concurrently
                break
        self._thread.join(timeout=5.0)
        if self._thread.is_alive():  # pragma: no cover - hung data_iter.next
            logging.warning(
                "mx-prefetch feed worker still running after close() "
                "(data iterator blocked in next()); resetting the iterator "
                "now may race the feed thread")

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is self._SENTINEL:
                if self._err is not None:
                    raise self._err
                return
            yield item


class _FeedBatchView:
    """Consumer-side view of a prefetched batch whose labels were copied out
    of the iterator's buffers (see _AsyncDeviceFeed buffer-reuse contract:
    labels are read for the metric update only after the step runs, well
    past the window in which a recycling iterator may rewrite them)."""

    __slots__ = ("_batch", "label")

    def __init__(self, batch, label):
        self._batch = batch
        self.label = label

    def __getattr__(self, name):
        return getattr(self._batch, name)


def _snapshot_batch(batch):
    label = []
    for l in batch.label:
        data = getattr(l, "data", None)
        if isinstance(data, np.ndarray):
            # numpy-backed: the iterator may rewrite the buffer in place
            label.append(NDArray(np.array(data, copy=True)))
        elif data is not None:
            # jax-backed: values are immutable, but a recycling iterator
            # can REBIND the holder's ._data — pin the current array in a
            # fresh holder (no copy needed)
            label.append(NDArray(data))
        else:  # pragma: no cover - non-NDArray labels pass through
            label.append(l)
    return _FeedBatchView(batch, label)


def _host_nbytes(arrays):
    """Bytes of the values of ``arrays`` that are in host memory (numpy, or
    a jax.Array on a CPU device) when ``place`` gets them: what it has to
    move. An array already on an accelerator counts 0."""
    total = 0
    for v in arrays.values():
        if isinstance(v, jax.Array):
            if all(d.platform == "cpu" for d in v.devices()):
                total += v.nbytes
        else:
            total += getattr(v, "nbytes", 0)
    return total


def _feed_waits(feed, epoch, tl):
    """The feed, with each blocking wait for the next ``(batch, arrays)`` as
    a ``fit.feed_wait`` span carrying the ``step`` it feeds (the last one of
    an epoch waits for the feed's end and feeds none). With a timeline the
    wait is also banked as the following step's ``data_wait`` phase."""
    it = iter(feed)
    for step in itertools.count():
        with telemetry_mod.phase("fit.feed_wait", epoch=epoch,
                                 step=step) as wait:
            try:
                item = next(it)
            except StopIteration:
                return
        if tl is not None:
            tl.note_data_wait(wait.end_ts - wait.start)
        yield item


def _spans_fit_start(fit):
    """``fit.start`` runs from ``fit``'s entry to its first epoch. It is
    opened around the call, so that every way out of ``fit``'s set-up closes
    it, and ``fit`` ends it at its first ``fit.epoch`` through
    ``self._fit_start`` (the close at the call's end is then a no-op)."""
    @functools.wraps(fit)
    def spanned(self, *args, **kwargs):
        with telemetry_mod.phase("fit.start") as self._fit_start:
            try:
                return fit(self, *args, **kwargs)
            finally:
                del self._fit_start
    return spanned


def _create_kvstore(kvstore, num_device, arg_params):
    """Reference: model.py:126-169 — resolve the kvstore strategy."""
    if kvstore is None:
        return None
    from .resilience.retry import RetryingKVStore

    if isinstance(kvstore, (kvstore_mod.KVStore, RetryingKVStore)):
        return kvstore
    if isinstance(kvstore, str):
        if num_device == 1 and "dist" not in kvstore:
            return None  # single device trains without any store
        return kvstore_mod.create(kvstore)
    raise TypeError("kvstore must be KVStore, str or None")


class FeedForward(BASE_ESTIMATOR):
    """Model estimator over a loss-headed Symbol (reference: model.py:465)."""

    def __init__(self, symbol, ctx=None, num_epoch=None, optimizer="sgd",
                 initializer=None, arg_params=None, aux_params=None,
                 allow_extra_params=False, begin_epoch=0,
                 compute_dtype=None, **kwargs):
        self.symbol = symbol
        if ctx is None:
            ctx = [current_context()]
        elif isinstance(ctx, Context):
            ctx = [ctx]
        self.ctx = ctx
        self.num_epoch = num_epoch
        self.optimizer = optimizer
        self.initializer = initializer or init_mod.Uniform(0.01)
        self.arg_params = arg_params
        self.aux_params = aux_params
        self.allow_extra_params = allow_extra_params
        self.begin_epoch = begin_epoch
        self.compute_dtype = compute_dtype
        self.kwargs = dict(kwargs)
        self._pred_fns = {}
        self._eval_fns = {}
        # fused train programs, keyed by everything that changes the compiled
        # step (bucket key, input names, mesh, metric, guards, pad policy,
        # optimizer identity) — the instance-level cache lets precompile()
        # AOT-warm the exact programs fit() will dispatch
        self._train_fns = {}
        self._graph_fps = {}  # bucket key -> graph fingerprint (labels)

    # -- pickling (reference behavior: notebooks pickle whole models) ---------
    def __getstate__(self):
        state = self.__dict__.copy()
        # compiled-step caches hold jitted closures; rebuilt lazily on use
        state["_pred_fns"] = {}
        state["_eval_fns"] = {}
        state["_train_fns"] = {}
        state["_graph_fps"] = {}
        state.pop("_optimizer_obj", None)
        state.pop("_opt_cache", None)
        # timelines hold the live hub (locks, deques) — session state, not
        # model state
        state.pop("telemetry", None)
        state.pop("_active_timeline", None)
        state.pop("health_monitor", None)
        state.pop("_fit_start", None)  # the open span of a fit in flight
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._pred_fns = {}
        self._eval_fns = {}
        self._train_fns = {}
        self._graph_fps = {}

    # -- parameter init -------------------------------------------------------
    def _init_params(self, input_shapes, overwrite=False):
        """Infer shapes and run the initializer (reference: model.py:556-569).

        Runs entirely on the HOST cpu backend (jax.default_device): the
        initializer dispatches many small ops per parameter, and on an
        accelerator each distinct shape would be its own compile and
        dispatch — ~270 arrays for a ResNet. Parameters upload once, in
        bulk, when the train state is built."""
        with telemetry_mod.phase("setup.init_params") as span:
            arg_shapes, _, aux_shapes = self.symbol.infer_shape(**input_shapes)
            arg_names = self.symbol.list_arguments()
            input_names = set(input_shapes.keys())
            param_names = [n for n in arg_names if n not in input_names]
            aux_names = self.symbol.list_auxiliary_states()
            shape_of = dict(zip(arg_names, arg_shapes))
            arg_params = dict(self.arg_params or {})
            aux_params = dict(self.aux_params or {})
            try:
                host = jax.local_devices(backend="cpu")[0]
            except RuntimeError:  # no cpu backend registered
                host = None
            scope = jax.default_device(host) if host is not None \
                else contextlib.nullcontext()
            initialised = 0
            with scope:
                for name in param_names:
                    if name in arg_params and not overwrite:
                        continue
                    arr = nd.zeros(shape_of[name], cpu())
                    self.initializer(name, arr)
                    arg_params[name] = arr
                    initialised += 1
                for name, shape in zip(aux_names, aux_shapes):
                    if name in aux_params and not overwrite:
                        continue
                    arr = nd.zeros(shape, cpu())
                    self.initializer(name, arr)
                    aux_params[name] = arr
                    initialised += 1
            self.arg_params, self.aux_params = arg_params, aux_params
            span.attrs["arrays"] = initialised
            return param_names, aux_names

    # -- device mesh ----------------------------------------------------------
    def _make_mesh(self, dist: bool):
        devices = [c.jax_device for c in self.ctx]
        if dist and jax.process_count() > 1:
            devices = jax.devices()  # span all hosts: dp over ICI+DCN
        # de-dup while keeping order (ctx list may alias the same chip)
        seen, devs = set(), []
        for d in devices:
            if d.id not in seen:
                seen.add(d.id)
                devs.append(d)
        if len(devs) <= 1:
            return None
        return Mesh(np.array(devs), ("dp",))

    # -- the fused train step -------------------------------------------------
    class _DeviceMetricAccum:
        """Host-side guard around a device metric accumulator: counts label
        instances per batch (statically known from shapes) and absorbs the
        on-device (sum, count) into the metric before its int32 counters
        could wrap — one extra pull per ~1e9 instances."""

        _FLUSH_AT = 2 ** 30

        def __init__(self, metric):
            self.metric = metric
            self.state = metric.device_init()
            self._pending = 0

        def after_batch(self, labels):
            self._pending += sum(int(np.prod(l.shape)) for l in labels)
            if self._pending > self._FLUSH_AT:
                self.metric.absorb_device_state(self.state)
                self.state = self.metric.device_init()
                self._pending = 0

        def finish(self):
            self.metric.absorb_device_state(self.state)
            self.state = self.metric.device_init()
            self._pending = 0

    def _symbol_for_bucket(self, bucket_key):
        """Symbol to compile for one bucket key; the base trainer has a
        single symbol (BucketingFeedForward generates one per key)."""
        del bucket_key
        return self.symbol

    def _fingerprint_for_bucket(self, bucket_key):
        if bucket_key not in self._graph_fps:
            self._graph_fps[bucket_key] = compile_mod.graph_fingerprint(
                self._symbol_for_bucket(bucket_key))
        return self._graph_fps[bucket_key]

    def _resolve_optimizer(self, param_names, batch_size, num_workers=1):
        """Optimizer object for this training configuration. Registry-name
        optimizers are cached per (name, effective batch, kwargs) so
        precompile() and a later fit() close the SAME object into their
        train steps — the program cache key includes the optimizer identity,
        and a fresh-but-identical object would orphan every warmed program."""
        opt = self.optimizer
        if not isinstance(opt, str):
            return opt
        sig = (opt, batch_size * num_workers,
               repr(sorted(self.kwargs.items(), key=lambda kv: kv[0])))
        cached = getattr(self, "_opt_cache", None)
        if cached is not None and cached[0] == sig:
            return cached[1]
        obj = opt_mod.create(opt, rescale_grad=1.0 / (batch_size * num_workers),
                             arg_names=list(param_names), **self.kwargs)
        self._opt_cache = (sig, obj)
        return obj

    def _state_order(self, apply_update=True):
        """``_stored_order`` of this model's symbol: what ``fit``,
        ``precompile`` and every train step over the one state agree on.
        Nothing is permuted where the optimizer runs elsewhere
        (update-on-kvstore: the weights arrive anew every step and
        gradients leave), nor for a model without one symbol."""
        if not apply_update or self.symbol is None:
            return {}
        return _stored_order(self.symbol)

    def _get_train_step(self, bucket_key, data_names, label_names, optimizer,
                        mesh, metric=None, apply_update=True, guard_cfg=None,
                        pad_policy=None, compression=None, overlap_plan=None,
                        comm_kernels=None, health_cfg=None):
        """The fused train step for one program configuration, built once
        and cached on the instance (reference analog: GraphExecutor's
        cached engine ops, one per shape). precompile() populates the same
        cache, so fit()'s first batch of a warmed shape compiles nothing."""
        key = (bucket_key, tuple(data_names), tuple(label_names),
               id(optimizer), mesh, None if metric is None
               else metric.device_key(), apply_update,
               None if guard_cfg is None else repr(vars(guard_cfg)),
               None if pad_policy is None else pad_policy.key(),
               None if compression is None else compression.key(),
               None if overlap_plan is None else overlap_plan.layout_key(),
               None if comm_kernels is None else comm_kernels.key(),
               None if health_cfg is None else health_cfg.key(),
               str(self.compute_dtype))
        if key not in self._train_fns:
            warmed = sum(getattr(fn, "_tracked", None) is not None
                         and fn._tracked.aot_programs
                         for fn in self._train_fns.values())
            if warmed:
                logging.warning(
                    "building train program (bucket %r) at step time even "
                    "though %d AOT-warmed program(s) exist — the warmup is "
                    "orphaned by a config mismatch: precompile()'s "
                    "eval_metric/guards/pad_policy/batch_end_callback must "
                    "match fit()'s", bucket_key, warmed)
            label = (f"train_step:{self._fingerprint_for_bucket(bucket_key)}"
                     + (f":bucket={bucket_key}" if bucket_key is not None
                        else ""))
            self._train_fns[key] = self._build_train_step(
                data_names, label_names, optimizer, mesh,
                symbol=self._symbol_for_bucket(bucket_key),
                metric_update=None if metric is None else metric.device_update,
                apply_update=apply_update, guard_cfg=guard_cfg,
                pad_policy=pad_policy, compression=compression,
                overlap_plan=overlap_plan, comm_kernels=comm_kernels,
                health_cfg=health_cfg, label=label)
        return self._train_fns[key]

    def _build_train_step(self, data_names, label_names, optimizer, mesh,
                          symbol=None, metric_update=None, apply_update=True,
                          guard_cfg=None, pad_policy=None, compression=None,
                          overlap_plan=None, comm_kernels=None,
                          health_cfg=None, label=None):
        """Compile the fused train step.

        With ``guard_cfg`` (resilience.GuardConfig) the program additionally
        threads a donated guard-state pytree and performs the non-finite
        step guard ON DEVICE: loss is scaled by the (dynamic) loss scale,
        one reduction pass over the gradients produces a single ``finite``
        flag, and every state update (params, optimizer, aux, metric)
        selects between new and old values with it — a NaN/Inf step is a
        no-op instead of a poisoned model, with no host sync in the loop.

        With ``pad_policy`` the program threads one extra input — the count
        of valid leading rows — and derives a (batch,) mask from it: the
        loss heads zero padded rows' injected gradients (ops/loss.py
        ``fwd_masked``) and the fused metric skips them, so a tail batch
        padded up to the training shape is metric- and loss-correct while
        reusing the ONE compiled program (no fresh shape, no recompile).

        With ``compression`` (a comm.CompressionSpec; mesh path only) the
        step is built as a shard_map over the 'dp' axis so the gradient
        sync is the EXPLICIT quantized allreduce from comm/allreduce.py
        instead of the partitioner's fp32 psum. Lossy modes additionally
        thread a donated comm-state pytree (the error-feedback residual,
        row-sharded so each device carries its own quantization error)
        through the carry exactly like the guard state; metric deltas and
        aux updates are psum/pmean'd so the fused device metric and
        BatchNorm statistics stay global. Donation and the zero-recompile
        steady-state invariant are preserved (tests/test_comm.py).

        With ``overlap_plan`` (comm.OverlapPlan) the gradient sync emits
        one independent quantized reduce-scatter/all-gather pair PER
        BUCKET in reverse-topological order instead of one fused pair, so
        XLA can hide each bucket's wire time under the rest of backward;
        the comm state becomes a dict of per-bucket residual ledgers
        (doc/developer-guide/comm.md, "Overlap scheduler").

        With ``health_cfg`` (telemetry.HealthConfig) the step additionally
        computes per-layer gradient/weight/update statistics + nonfinite
        counts ON DEVICE (telemetry.health.device_stats) and threads the
        resulting tiny pytree through the donated carry exactly like the
        guard state — fixed shapes, so the armed zero-recompile epoch
        stays green, and the stats live in the same XLA program, so the
        jaxpr-audit FLOP table prices them and MFU stays honest. On the
        compressed shard_map path the stats read the post-allreduce
        (replicated) gradients — what the optimizer really consumed — so
        no extra collective crosses the wire.
        """
        symbol = symbol if symbol is not None else self.symbol
        # the leaves the state holds with their axes permuted, and the
        # shapes they then have: the graph takes each back to its declared
        # order where its operator reads it, and a leaf handed over in its
        # declared shape is refused (run), never transposed again or
        # compiled for
        order = self._state_order(apply_update)
        compute_dtype = self.compute_dtype
        graph_fn = _build_graph_fn(symbol, is_train=True, stored=order,
                                   stored_dtype=compute_dtype)
        health_groups = None
        health_heads = ()
        if health_cfg is not None:
            # layer groups derive from the SAME base the fit loop's host
            # side uses (symbol arguments minus inputs == param_names), so
            # the (L,) stat vectors index identically on both sides
            inputs = set(data_names) | set(label_names)
            health_groups = telemetry_mod.health.layer_groups(
                n for n in symbol.list_arguments() if n not in inputs)
            # loss heads + their label inputs: the TRUE scalar loss for
            # the health stream. The seed-ones cotangent reduced below is
            # a gradient seed — for softmax heads it is CONSTANT (the
            # outputs are probabilities), useless to a spike detector.
            health_heads = tuple(
                (i, node.op, node.inputs[1][0].name)
                for i, (node, _k) in enumerate(symbol._heads)
                if not node.is_variable
                and getattr(node.op, "is_loss", False)
                and len(node.inputs) > 1 and node.inputs[1][0].is_variable)

        def _health_loss_value(outs, batch, mask):
            total = None
            for i, op, lbl in health_heads:
                if lbl not in batch:
                    continue
                lv = op.loss_value(outs[i], batch[lbl], mask=mask)
                if lv is None:
                    continue
                total = lv if total is None else total + lv
            return total
        stored_shapes = {
            k: tuple(self.arg_params[k].shape[a] for a in axes)
            for k, axes in order.items()}

        def check_stored(params):
            for k, shape in stored_shapes.items():
                if tuple(params[k].shape) != shape:
                    raise MXNetError(
                        f"train step: parameter {k!r} arrived as "
                        f"{tuple(params[k].shape)}; the step's state holds "
                        f"it in its stored order {order[k]}, as {shape} "
                        "(FeedForward._state_order)")

        comm_spec = compression if mesh is not None else None
        in_shard = comm_spec is not None  # compute body runs inside shard_map
        axis_size = int(mesh.shape["dp"]) if mesh is not None else 1
        has_cstate = in_shard and comm_spec.error_feedback
        # False (not None): the caller resolved the kernel gate once; None
        # would re-read MXNET_TPU_COMM_KERNELS at trace time and could arm
        # a path the program cache key doesn't know about
        comm_kernels = comm_kernels if comm_kernels is not None else False

        def compute(params, opt_state, aux, batch, rng, lr, mstate, gstate,
                    valid, cstate=None, hstate=None):
            from . import comm as comm_mod

            scale = gstate["scale"] if guard_cfg is not None else None
            mask = None
            if valid is not None:
                rows_of = label_names[0] if label_names else data_names[0]
                n_rows = batch[rows_of].shape[0]
                row0 = jax.lax.axis_index("dp") * n_rows if in_shard else 0
                mask = ((row0 + jnp.arange(n_rows)) < valid).astype(
                    jnp.float32)

            def loss_fn(p):
                if compute_dtype is not None:
                    p_c = {k: (v.astype(compute_dtype)
                               if jnp.issubdtype(v.dtype, jnp.floating)
                               and k not in order else v)
                           for k, v in p.items()}
                    # floating data only: integer ids above 256 are not
                    # whole numbers in bfloat16 (predict and eval do the same)
                    b_c = {k: (v.astype(compute_dtype)
                               if k in data_names
                               and jnp.issubdtype(v.dtype, jnp.floating)
                               else v)
                           for k, v in batch.items()}
                else:
                    p_c, b_c = p, batch
                outs, new_aux = graph_fn({**p_c, **b_c}, aux, rng, mask)
                # seed-ones cotangent: loss heads inject their own gradient
                with jax.named_scope("loss/sum"):
                    loss = sum(jnp.sum(o.astype(jnp.float32)) for o in outs)
                    if scale is not None:
                        loss = loss * scale
                return loss, (outs, new_aux)

            (loss, (outs, new_aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if scale is not None:
                inv = 1.0 / scale
                grads = {k: g * inv.astype(g.dtype) for k, g in grads.items()}
            new_cstate = cstate
            if in_shard:
                # explicit gradient sync (sum semantics, matching the
                # partitioner-inserted psum; the optimizer's rescale_grad
                # turns the sum into the mean). Scoped "comm/..." so the
                # device-time profiler attributes the wire's device cost.
                with jax.named_scope("comm/allreduce"):
                    if overlap_plan is not None:
                        grads, resid = comm_mod.overlap_allreduce(
                            grads, cstate["resid"] if has_cstate else None,
                            overlap_plan, axis_name="dp", average=False,
                            kernels=comm_kernels)
                        if has_cstate:
                            new_cstate = {"resid": resid}
                    elif has_cstate:
                        grads, resid = comm_mod.error_feedback_allreduce(
                            grads, cstate["resid"], comm_spec,
                            axis_name="dp", axis_size=axis_size,
                            average=False, kernels=comm_kernels)
                        new_cstate = {"resid": resid}
                    else:
                        grads = comm_mod.compressed_allreduce(
                            grads, comm_spec, axis_name="dp",
                            axis_size=axis_size, average=False,
                            kernels=comm_kernels)
                    loss = jax.lax.psum(loss, "dp")
                    new_aux = jax.tree_util.tree_map(
                        lambda a: jax.lax.pmean(a, "dp")
                        if jnp.issubdtype(a.dtype, jnp.floating) else a,
                        new_aux)
            h_loss = None
            if health_cfg is not None:
                # true training loss while the head outputs are still in
                # hand (the metric fold below drops them)
                with jax.named_scope("health/loss"):
                    h_loss = _health_loss_value(outs, batch, mask)
                    if h_loss is None:
                        # no loss head priced itself: the seed scalar is
                        # the only signal left (already psum'd on the
                        # shard path)
                        h_loss = loss if scale is None else loss / scale
                    elif in_shard:
                        h_loss = jax.lax.psum(h_loss, "dp")
            finite = None
            if guard_cfg is not None and guard_cfg.skip_nonfinite:
                # scaled loss + unscaled grads: overflow in either shows up
                with jax.named_scope("guards/finite"):
                    finite = guards_mod.finite_flag(loss, grads)
            if apply_update:
                with jax.named_scope("optimizer/update"):
                    new_params, new_opt_state = optimizer.apply(
                        params, grads, opt_state, lr)
                if finite is not None:
                    with jax.named_scope("guards/select"):
                        new_params = guards_mod.guard_select(
                            finite, new_params, params)
                        new_opt_state = guards_mod.guard_select(
                            finite, new_opt_state, opt_state)
            else:
                # update-on-kvstore (dist_async): grads come back in the
                # params slot; the parameter host applies the optimizer
                new_params, new_opt_state = grads, opt_state
            if finite is not None:
                # aux (e.g. batchnorm moving stats) is updated by the
                # forward pass on BOTH paths — a NaN step must not poison
                # it even when the optimizer update happens elsewhere
                with jax.named_scope("guards/select"):
                    new_aux = guards_mod.guard_select(finite, new_aux, aux)
            if metric_update is not None:
                # fold metric accumulation into the same XLA program — no
                # per-batch host pull (every pull is a device round-trip) —
                # and drop the forward outputs from the program: nothing
                # reads them, so XLA needn't materialize them every step
                with jax.named_scope("metric/update"):
                    labels = [batch[n] for n in label_names]
                    outs_f32 = [o.astype(jnp.float32) for o in outs]
                    base = mstate
                    if in_shard:
                        # device metrics are additive (sum, count)
                        # accumulators: fold each shard's DELTA from a zero
                        # state, psum it, and add — updating from mstate
                        # per shard would count the replicated base
                        # axis_size times
                        base = jax.tree_util.tree_map(jnp.zeros_like,
                                                      mstate)
                    if mask is not None:
                        new_mstate = metric_update(base, labels, outs_f32,
                                                   valid=mask)
                    else:
                        new_mstate = metric_update(base, labels, outs_f32)
                    if in_shard:
                        delta = jax.tree_util.tree_map(
                            lambda d: jax.lax.psum(d, "dp"), new_mstate)
                        new_mstate = jax.tree_util.tree_map(jnp.add, mstate,
                                                            delta)
                    if finite is not None:
                        new_mstate = guards_mod.guard_select(
                            finite, new_mstate, mstate)
                    mstate = new_mstate
                outs = ()
            if guard_cfg is not None:
                with jax.named_scope("guards/update"):
                    gstate = guards_mod.update_guard_state(
                        guard_cfg, gstate,
                        finite if finite is not None else jnp.bool_(True))
            new_hstate = hstate
            if health_cfg is not None:
                # per-layer stats from the grads the optimizer consumed
                # (replicated post-allreduce on the shard path — already
                # global, nothing extra crosses the wire) and the
                # post-guard-select params: a skipped step reads as
                # update_ratio 0 while its grad norms still show the
                # explosion that tripped the guard
                with jax.named_scope("health/stats"):
                    new_hstate = telemetry_mod.health.device_stats(
                        health_groups, params, grads, new_params, h_loss)
            return (new_params, new_opt_state, new_aux, outs, mstate, gstate,
                    new_cstate, new_hstate)

        # signature tail: [gstate][cstate][hstate][valid] — donated indices
        # stay fixed for the existing configurations; ``valid`` (a scalar)
        # is never donated
        padded = pad_policy is not None
        has_g = guard_cfg is not None
        has_h = health_cfg is not None
        if in_shard:
            return self._finish_sharded_step(
                compute, mesh, comm_spec, axis_size, guard_cfg, has_cstate,
                padded, label, overlap_plan=overlap_plan, has_health=has_h,
                check_stored=check_stored)

        def step(params, opt_state, aux, batch, rng, lr, mstate, *rest):
            i = 0
            gstate = hstate = valid = None
            if has_g:
                gstate = rest[i]
                i += 1
            if has_h:
                hstate = rest[i]
                i += 1
            if padded:
                valid = rest[i]
            res = compute(params, opt_state, aux, batch, rng, lr, mstate,
                          gstate, valid, None, hstate)
            out = res[:5]
            if has_g:
                out += (res[5],)
            if has_h:
                out += (res[7],)
            return out

        donate = (0, 1, 2, 6) + tuple(7 + j for j in range(has_g + has_h))

        if mesh is None:
            # Single-device path: pin everything to the ctx device. Data
            # iterators hand over host-committed arrays, and jit follows
            # committed inputs — without this, one cpu-committed batch
            # silently drags the WHOLE train step onto the host backend.
            dev = self.ctx[0].jax_device
            jitted = compile_mod.tracked_jit(step, label=label,
                                             donate_argnums=donate)

            def run(params, opt_state, aux, batch, rng, lr, mstate, *rest):
                check_stored(params)
                batch = {k: _to_dev(v, dev) for k, v in batch.items()}
                params = {k: _to_dev(v, dev) for k, v in params.items()}
                aux = {k: _to_dev(v, dev) for k, v in aux.items()}
                # opt/metric/guard state must be COMMITTED to the ctx
                # device too: the jit cache keys on arg placement, and the
                # fresh uncommitted accumulators each epoch starts with
                # would otherwise recompile the whole step once per epoch
                # (found by the compile registry; see test_compile.py).
                # Steady state (all outputs of the previous step, already
                # committed) skips the tree walk on a first-leaf probe.
                to_dev = lambda t: (t if not _needs_commit(t, dev)  # noqa: E731
                                    else jax.tree_util.tree_map(
                                        lambda v: _to_dev(v, dev), t))
                opt_state = to_dev(opt_state)
                mstate = to_dev(mstate)
                rest = tuple(to_dev(r) if isinstance(r, dict)
                             else _to_dev(jnp.asarray(r), dev) for r in rest)
                # lr as a typed scalar: keeps the call signature identical
                # to what precompile() lowers for, so AOT-warmed programs
                # dispatch without consulting the jit cache at all
                return jitted(params, opt_state, aux, batch, rng,
                              jnp.float32(lr), mstate, *rest)

            run._tracked = jitted
            return run
        repl = NamedSharding(mesh, P())
        batch_sh = NamedSharding(mesh, P("dp"))
        jitted = compile_mod.tracked_jit(step, label=label,
                                         donate_argnums=donate)

        def run(params, opt_state, aux, batch, rng, lr, mstate, *rest):
            check_stored(params)
            batch = {k: _place(v, batch_sh if np.ndim(v) else repl)
                     for k, v in batch.items()}
            if _needs_place(params, mesh):
                params = jax.tree_util.tree_map(lambda v: _place(v, repl), params)
            if _needs_place(opt_state, mesh):
                opt_state = jax.tree_util.tree_map(lambda v: _place(v, repl), opt_state)
            if _needs_place(aux, mesh):
                aux = jax.tree_util.tree_map(lambda v: _place(v, repl), aux)
            if _needs_place(mstate, mesh):
                mstate = jax.tree_util.tree_map(lambda v: _place(v, repl), mstate)
            rest = tuple(
                (jax.tree_util.tree_map(lambda v: _place(v, repl), r)
                 if _needs_place(r, mesh) else r) if isinstance(r, dict)
                else _place(jnp.asarray(r), repl) for r in rest)
            return jitted(params, opt_state, aux, batch, rng, jnp.float32(lr),
                          mstate, *rest)

        run._tracked = jitted
        return run

    def _finish_sharded_step(self, compute, mesh, comm_spec, axis_size,
                             guard_cfg, has_cstate, padded, label,
                             overlap_plan=None, has_health=False,
                             check_stored=lambda params: None):
        """Assemble the compressed-comm train step: ``jit(shard_map(...))``
        over the dp axis (see _build_train_step's compression note).

        In/out specs mirror the signature tail — params/opt/aux/metric/
        guard state replicated, batch and forward outputs row-sharded, the
        error-feedback comm state row-sharded so each device keeps its own
        residual. Donation matches the SPMD path; the program's exact wire
        plan registers with the comm registry at first dispatch and every
        call counts one sync step (``comm.comm_stats()``)."""
        from . import comm as comm_mod
        from .compat import shard_map as _shard_map

        has_g = guard_cfg is not None
        has_h = has_health

        def step(params, opt_state, aux, batch, rng, lr, mstate, *rest):
            i = 0
            gstate = cstate = hstate = valid = None
            if has_g:
                gstate = rest[i]
                i += 1
            if has_cstate:
                cstate = rest[i]
                i += 1
            if has_h:
                hstate = rest[i]
                i += 1
            if padded:
                valid = rest[i]
            res = compute(params, opt_state, aux, batch, rng, lr, mstate,
                          gstate, valid, cstate, hstate)
            out = res[:5]
            if has_g:
                out += (res[5],)
            if has_cstate:
                out += (res[6],)
            if has_h:
                out += (res[7],)
            return out

        tail_in = (P(),) * has_g + (P("dp"),) * has_cstate \
            + (P(),) * has_h + (P(),) * padded
        in_specs = (P(), P(), P(), P("dp"), P(), P(), P()) + tail_in
        out_specs = (P(), P(), P(), P("dp"), P()) \
            + (P(),) * has_g + (P("dp"),) * has_cstate + (P(),) * has_h
        sharded = _shard_map(step, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
        donate = (0, 1, 2, 6) + tuple(
            7 + j for j in range(has_g + has_cstate + has_h))
        jitted = compile_mod.tracked_jit(sharded, label=label,
                                         donate_argnums=donate)
        repl = NamedSharding(mesh, P())
        batch_sh = NamedSharding(mesh, P("dp"))
        csh = NamedSharding(mesh, P("dp"))
        reg = comm_mod.registry()
        plan_state = {"registered": False}

        def run(params, opt_state, aux, batch, rng, lr, mstate, *rest):
            if not plan_state["registered"]:
                reg.register_plan(
                    label,
                    overlap_plan.wire_plan() if overlap_plan is not None
                    else comm_mod.allreduce_plan(
                        comm_mod.flat_size(params), axis_size, comm_spec))
                plan_state["registered"] = True
            reg.record_step(label)
            check_stored(params)
            batch = {k: _place(v, batch_sh if np.ndim(v) else repl)
                     for k, v in batch.items()}
            place_repl = lambda t: (jax.tree_util.tree_map(  # noqa: E731
                lambda v: _place(v, repl), t) if _needs_place(t, mesh) else t)
            params = place_repl(params)
            opt_state = place_repl(opt_state)
            aux = place_repl(aux)
            mstate = place_repl(mstate)
            placed, i = [], 0
            if has_g:
                placed.append(place_repl(rest[i]))
                i += 1
            if has_cstate:
                c = rest[i]
                i += 1
                if _needs_place(c, mesh):
                    c = jax.tree_util.tree_map(lambda v: _place(v, csh), c)
                placed.append(c)
            if has_h:
                placed.append(place_repl(rest[i]))
                i += 1
            if padded:
                placed.append(_place(jnp.asarray(rest[i]), repl))
            return jitted(params, opt_state, aux, batch, rng,
                          jnp.float32(lr), mstate, *placed)

        run._tracked = jitted
        return run

    def _async_pull_params(self, kv, param_names):
        """Pull current weights from the dist_async parameter host into
        self.arg_params (one round trip for all keys)."""
        pulled = kv.pull_many(param_names)
        for name in param_names:
            self.arg_params[name] = NDArray(pulled[name])

    def _build_pred_step(self, mesh, symbol=None, label=None):
        graph_fn = _build_graph_fn(symbol if symbol is not None else self.symbol,
                                   is_train=False)
        compute_dtype = self.compute_dtype

        def step(params, aux, batch):
            if compute_dtype is not None:
                params = {k: (v.astype(compute_dtype)
                              if jnp.issubdtype(v.dtype, jnp.floating) else v)
                          for k, v in params.items()}
                batch = {k: v.astype(compute_dtype) if jnp.issubdtype(v.dtype, jnp.floating) else v
                         for k, v in batch.items()}
            outs, _ = graph_fn({**params, **batch}, aux, jnp.zeros((2,), jnp.uint32))
            return tuple(o.astype(jnp.float32) for o in outs)

        return compile_mod.tracked_jit(step, label=label)

    # -- fit ------------------------------------------------------------------
    @_spans_fit_start
    def fit(self, X, y=None, eval_data=None, eval_metric="accuracy",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            logger=None, work_load_list=None, batch_size=128,
            sharded_checkpoint_dir=None, guards=None, pad_policy=None,
            compression=None, overlap=None, comm_kernels=None,
            telemetry=None, elastic=None, controller=None, health=None,
            profile=None, shard_audit=None,
            checkpoint_every_n_steps=None):
        """Train (reference: model.py:669 fit -> _train_multi_device:171).

        ``work_load_list`` is accepted for parity and ignored: XLA SPMD
        shards the batch evenly (heterogeneous device splits don't exist on a
        TPU slice).

        ``sharded_checkpoint_dir``: when set, the LIVE device state (params
        may be mesh-sharded) is checkpointed per epoch via
        utils.checkpoint.save_sharded, and training auto-resumes from the
        newest complete *valid* step in that directory (SURVEY.md §5's
        TPU-native checkpoint/resume: every host writes only its shards;
        torn/corrupt steps are skipped). SIGTERM mid-epoch flushes a final
        checkpoint at the next step boundary and raises TrainingPreempted,
        so a relaunch resumes instead of losing the epoch.

        ``guards``: step-guard control — None (default; env gate
        MXNET_TPU_GUARDS), True (default resilience.GuardConfig), or a
        GuardConfig. With guards on, non-finite steps are skipped on
        device (with optional dynamic loss-scale backoff), transient
        mid-step failures are retried, and a watchdog can bound step time
        (doc/developer-guide/resilience.md).

        ``pad_policy``: tail-batch shape control — None (default; env gate
        MXNET_TPU_PAD_POLICY), True/'bucket'/'pow2', or a
        utils.compile.PadPolicy. With a policy, a final partial batch is
        padded up to the training shape and masked (loss- and
        metric-correct: padded rows inject no gradient and are excluded
        from the metric) instead of compiling a second program for the odd
        shape (doc/developer-guide/compile_cache.md).

        ``compression``: gradient-sync wire control — None (default; env
        gate MXNET_TPU_GRAD_COMPRESSION), True/'bf16'/'int8'/'twobit', a
        reference-style dict ``{'type': '2bit', 'threshold': 0.5}``, or a
        comm.CompressionSpec. On a multi-device mesh the fused step syncs
        one quantized bucket instead of the fp32 psum (int8/twobit thread
        an error-feedback residual through the step carry for convergence
        parity); with kvstore='dist_async' the spec is forwarded to
        ``kv.set_gradient_compression`` so pushes cross the socket
        quantized. Wire accounting: ``comm.comm_stats()`` and the
        per-epoch ``Comm:`` log line (doc/developer-guide/comm.md).

        ``overlap``: comm/compute overlap control — None (default; env
        gate ``MXNET_TPU_COMM_OVERLAP``), True (4 MB buckets), an int
        bucket byte cap, or a comm.OverlapConfig. On the mesh path (needs
        ``compression``) the fused step syncs one independent quantized
        reduce-scatter/all-gather pair per gradient bucket, scheduled in
        reverse-topological order so XLA hides wire time under backward;
        error-feedback residuals become per-bucket ledgers (checkpointed
        with the optimizer state, invalidated when the bucket plan
        changes). With kvstore='dist_async' it arms STALE-SYNC pipelining:
        each step's push+pull runs on a background thread and the step
        trains on weights one round stale — the timeline's ``wire`` phase
        shows only the un-hidden tail, the hidden portion lands as an
        ``overlap`` sub-span, and ``comm_overlap_efficiency`` gauges how
        much of the wire was hidden (doc/developer-guide/comm.md,
        "Overlap scheduler").

        ``comm_kernels``: fused Pallas quantize/dequantize for the
        compressed gradient sync — None (default; env gate
        ``MXNET_TPU_COMM_KERNELS``), True, an int VMEM-block element
        cap, or a comm.CommKernelConfig. Same wire bits as the reference
        codecs (bitwise, test-enforced); the encode/decode stages stop
        costing full-slab elementwise HLO passes
        (doc/developer-guide/kernels.md). Only meaningful with a lossy
        ``compression`` mode on the mesh path.

        ``telemetry``: observability control — None (default; env gate
        ``MXNET_TPU_TELEMETRY``), True, a JSONL path, or a
        telemetry.TelemetryConfig. When on, the loop records a
        StepTimeline (one span per step: data_wait / dispatch / device /
        [kvstore] / host phases, guard retries as instant events), logs
        per-epoch ``MFU:`` and ``Goodput:`` lines (FLOPs from the jaxpr
        audit table; badput attributed to compile, data stalls, checkpoint
        flushes, and wasted steps), and exports through the metrics hub
        (Prometheus / JSONL / Chrome trace). The timeline lands on
        ``self.telemetry`` (``.dump_chrome_trace(path)``,
        ``.dump_jsonl(path)``). Exact device timing blocks on each step's
        outputs — that trades feed/compute overlap for attribution
        (doc/developer-guide/telemetry.md); ``TelemetryConfig(sync=False)``
        keeps the overlap.

        ``elastic``: mid-run world resizing — None (default; env gate
        ``MXNET_TPU_ELASTIC``), True, or a
        resilience.elastic.ElasticCoordinator (pass your own to drive
        kills/joins from callbacks or heartbeats). When armed, the loop
        polls the coordinator once per step; on a membership change it
        quiesces the in-flight step, re-shards params/optimizer state
        from the newest CRC-manifest checkpoint onto the new ``dp`` axis
        (error-feedback residuals survive only when their layout key
        still matches — a changed axis drops them safely), re-derives the
        overlap/bucket wire plans, re-runs AOT warmup for the new axis
        through TrackedJit (growing back to a seen axis reuses the
        still-warm executables), and resumes in the same process — the
        interrupted epoch is redone on the new world, the same
        epoch-granular contract as preemption resume. Requires
        ``sharded_checkpoint_dir`` and a multi-device ctx list; downtime
        is priced into goodput as a ``resize`` badput bucket and appears
        in traces as coordinator spans
        (doc/developer-guide/resilience.md, "Elastic training").

        ``controller``: the self-driving fleet policy loop — None
        (default; env gate ``MXNET_TPU_CONTROLLER``, value ``dry`` for
        recommend-only), True, a FleetControllerConfig, or a
        resilience.FleetController. When armed, the loop ticks the
        controller once per step (unless it runs on its own
        ``mx-fleet-ctl`` thread): it watches the live telemetry
        (streaming straggler blame, goodput-per-chip, comm:compute
        ratio), evicts consistently-blamed stragglers and backfills
        them through the elastic coordinator (pass ``elastic=`` to arm
        the membership levers), and stages compression-tier/overlap-cap
        changes that this loop applies through the AOT re-warm path.
        Every decision is a ``controller`` event + flight-recorder
        incident; its own circuit breaker freezes actuation (never the
        fit) on failures or goodput regressions
        (doc/developer-guide/resilience.md, "Fleet controller").

        ``health``: training-health observability — None (default; env
        gate ``MXNET_TPU_HEALTH``), True, or a telemetry.HealthConfig.
        When armed, the fused step computes per-layer gradient norm,
        weight norm, update:weight ratio, and nonfinite counts ON DEVICE
        (donated through the step carry — zero-recompile invariant
        preserved, stats priced into the MFU FLOP table), and a streaming
        HealthMonitor (``self.health_monitor``) runs EWMA/MAD anomaly
        detectors on the host: loss spikes, per-layer gradient
        explosions, dead layers, slow divergence drift, NaN/Inf — each
        hit a ``health_anomaly`` flight-recorder incident naming the
        layer, emitted BEFORE the guard-skip event it explains
        (doc/developer-guide/telemetry.md, "Training health").

        ``profile``: measured device-time attribution — None (default;
        env gate ``MXNET_TPU_PROFILE``, an integer value = window steps),
        True, an int (window steps), or a telemetry.ProfileConfig. When
        armed, the loop opens ONE bounded K-step capture window through
        ``jax.profiler`` after warmup on a compile-quiet step, joins the
        measured per-instruction device time back to layers/kernels via
        the named-scope HLO metadata (coverage ratio + explicit
        unattributed row), produces measured roofline rows
        (``source: "measured"``) against the jaxpr-audit/kernel-registry
        FLOP models, and reconciles measured vs modeled MFU. The window's
        wall time is priced as a ``profile`` badput bucket; the report
        lands on ``self.profile_report`` and as a ``profile`` summary
        event + ``profile_*`` gauges (doc/developer-guide/telemetry.md,
        "Device profiling").

        ``checkpoint_every_n_steps``: step-granular async checkpoint
        cadence (ISSUE 17) — None (default; env gate
        ``MXNET_TPU_CKPT_STEPS``) or an int N. When armed (requires
        ``sharded_checkpoint_dir``), every N optimizer steps the loop
        takes ONE blocking device->host snapshot and returns to training
        while the ``mx-ckpt-writer`` thread persists it to the atomic
        CRC-manifest format (T2), prunes old steps
        (``MXNET_TPU_CKPT_KEEP``), and the snapshot is replicated to a
        neighbor rank's RAM over the kvstore ``replica`` op (T1) so an
        elastic resize restores without a disk read. Step metadata
        (data-iterator position, RNG state, loss scale, ``num_update``)
        makes resume mid-epoch and bitwise-equal to a checkpoint-replay
        reference; writer failures surface as ``checkpoint`` flight
        incidents, never as training exceptions
        (doc/developer-guide/resilience.md, "Async + multi-tier
        checkpointing")."""
        del work_load_list
        guard_cfg = guards_mod.GuardConfig.resolve(guards)
        health_cfg = telemetry_mod.HealthConfig.resolve(health)
        profile_cfg = telemetry_mod.ProfileConfig.resolve(profile)
        pad_policy = compile_mod.PadPolicy.resolve(pad_policy)
        tcfg = telemetry_mod.TelemetryConfig.resolve(telemetry)
        from . import comm as comm_mod

        comm_spec = comm_mod.CompressionSpec.resolve(compression)
        overlap_cfg = comm_mod.OverlapConfig.resolve(overlap)
        kern_cfg = comm_mod.CommKernelConfig.resolve(comm_kernels)
        from .resilience import ckpt_async as ckpt_plane_mod

        ckpt_every = ckpt_plane_mod.resolve_every(checkpoint_every_n_steps)
        resume_opt_leaves, resume_num_update = None, 0
        resume_scale = None
        resume_comm_state, resume_comm_layout = None, None
        resume_batches_done = 0
        if sharded_checkpoint_dir is not None:
            from .utils import checkpoint as ckpt_mod

            last = ckpt_mod.latest_step(sharded_checkpoint_dir)
            if last is not None:
                # FeedForward keeps params replicated (dp training), so the
                # host-numpy restore is the right cost here; mesh-sharded
                # restore stays available via utils.checkpoint directly.
                loaded, laux, _, meta, resume_opt_leaves, \
                    resume_comm_state = ckpt_mod.load_sharded(
                        sharded_checkpoint_dir, last, with_comm=True)
                resume_comm_layout = meta.get("comm_layout")
                self.arg_params = {k: NDArray(np.asarray(v))
                                   for k, v in loaded.items()}
                self.aux_params = {k: NDArray(np.asarray(v))
                                   for k, v in laux.items()}
                self.begin_epoch = int(meta.get("epoch", last))
                resume_num_update = int(meta.get("num_update", 0))
                resume_scale = meta.get("loss_scale")
                # step-granular resume (ISSUE 17): a mid-epoch snapshot
                # records how many batches the interrupted epoch already
                # trained and the RNG key words at the boundary — the
                # resumed loop fast-forwards the iterator and draws the
                # same per-step subkeys the original run would have
                resume_batches_done = int(meta.get("batches_done", 0))
                if meta.get("rng_state") is not None:
                    random_mod.set_state(meta["rng_state"])
                (logger or logging).info(
                    "resumed sharded checkpoint step %d (epoch %d, "
                    "batches_done %d)", last, self.begin_epoch,
                    resume_batches_done)
        if logger is None:
            logger = logging
        train_data = _init_iter(X, y, batch_size, shuffle=True)
        if train_data.batch_size:
            batch_size = train_data.batch_size

        data_shapes = dict(train_data.provide_data)
        label_shapes = dict(train_data.provide_label)
        input_shapes = {**data_shapes, **label_shapes}
        data_names = list(data_shapes.keys())
        label_names = list(label_shapes.keys())
        param_names, aux_names = self._init_params(input_shapes)

        kv = _create_kvstore(kvstore, len(self.ctx), self.arg_params)
        num_workers = kv.num_workers if kv is not None else 1
        if kv is not None and (num_workers > 1 or kv.rank):
            # a distributed kvstore is the rank/world authority: every hub
            # metric family and JSONL event gets labeled with it (a
            # thread-local telemetry.rank_scope, e.g. the in-process
            # multi-worker harness, still overrides per thread; a local
            # store's hardcoded 0/1 must not clobber a real identity)
            telemetry_mod.set_world(kv.rank, num_workers)
        async_kv = kv is not None and kv.type == "dist_async"
        # dist_async: no BSP collective — each worker trains against the
        # parameter host at its own pace, so the mesh stays process-local
        # (reference: update-on-arrival, kvstore_dist_server.h:194-202)
        mesh = self._make_mesh(
            dist=kv is not None and "dist" in kv.type and not async_kv)
        train_devs = [self.ctx[0].jax_device] if mesh is None \
            else list(mesh.devices.flat)
        logger.info("fit: training on %d device(s): %s", len(train_devs),
                    ", ".join(str(d) for d in train_devs))
        if train_devs[0].platform == "cpu" and jax.default_backend() != "cpu":
            # ctx=None keeps the reference default, cpu(0)
            logger.warning(
                "fit: ctx resolves to the host CPU although this process "
                "has a %s backend; pass ctx=mx.tpu() to train on it",
                jax.default_backend())
        if num_workers > 1 and jax.process_count() > 1:
            # rank 0's initialization wins, like kvstore.init from rank 0
            # (reference: kvstore_dist.h:49-60) — otherwise per-process RNGs
            # would silently train diverged replicas.
            from jax.experimental import multihost_utils

            names = sorted(self.arg_params)
            aux_ns = sorted(self.aux_params)
            flat = multihost_utils.broadcast_one_to_all(
                tuple([self.arg_params[k].asnumpy() for k in names] +
                      [self.aux_params[k].asnumpy() for k in aux_ns]))
            for k, v in zip(names + aux_ns, flat):
                (self.arg_params if k in names else self.aux_params)[k] = \
                    NDArray(np.asarray(v))

        optimizer = self._resolve_optimizer(param_names, batch_size,
                                            num_workers)
        self._optimizer_obj = optimizer

        async_comm_spec = None
        if comm_spec is not None and async_kv:
            # host-transport compression: grads cross the parameter-host
            # socket quantized+bucketed (kvstore_async.py); no in-jit comm
            if hasattr(kv, "set_gradient_compression"):
                # fit-setup wiring of the USER'S static spec, before any
                # step runs — mid-run tier changes go through the
                # controller's retier lever
                kv.set_gradient_compression(comm_spec)  # mxlint: disable=MX311 - launch config, not mid-run actuation
                async_comm_spec = comm_spec
            comm_spec = None
        elif comm_spec is not None and mesh is None:
            logger.info("compression=%s ignored: single-device training "
                        "moves no gradient bytes over a wire",
                        comm_spec.mode)
            comm_spec = None

        # overlap= resolves per path: dist_async -> stale-sync pipelining
        # (pushes lag one step behind compute); mesh + compression -> the
        # in-jit per-bucket schedule; anything else has no wire to hide
        stale_sync = False
        if overlap_cfg is not None and async_kv:
            if hasattr(kv, "push_pull_stale"):
                stale_sync = True
                logger.info("overlap: stale-sync armed — bucket pushes lag "
                            "one step behind compute (weights one round "
                            "stale; ps-lite async heritage)")
            overlap_cfg = None
        elif overlap_cfg is not None and comm_spec is None:
            if mesh is not None:
                logger.info("overlap= ignored: the overlapped schedule "
                            "pipelines the quantized per-bucket sync — set "
                            "compression= to arm it")
            overlap_cfg = None
        overlap_plan = None
        if overlap_cfg is not None:
            overlap_plan = comm_mod.plan_overlap(
                {k: tuple(self.arg_params[k].shape) for k in param_names},
                comm_spec, int(mesh.shape["dp"]),
                max_bytes=overlap_cfg.bucket_bytes, symbol=self.symbol)
            logger.info(
                "overlap: %d bucket(s) scheduled reverse-topologically "
                "(cap %d bytes; per-bucket reduce-scatter/all-gather "
                "rides under backward)", overlap_plan.num_buckets,
                overlap_cfg.bucket_bytes)

        # opt-in shard audit (ISSUE 16): before the first dispatch of each
        # program, mxlint Pass 5 reconciles the warmed executable's
        # collective set against the declared comm plan and raises on
        # MX802 drift — no step runs on a program whose wire traffic the
        # plan cannot vouch for
        from .analysis.sharding import shard_audit_enabled
        shard_audit_on = shard_audit_enabled(shard_audit) \
            and mesh is not None
        _shard_audited: set = set()

        if async_kv:
            if sharded_checkpoint_dir is not None and num_workers > 1:
                # single-worker dist_async (one replica, one writer) is
                # exactly the resilience-test topology and is safe
                raise MXNetError(
                    "sharded_checkpoint_dir is not supported with "
                    "multi-worker kvstore='dist_async': workers hold "
                    "diverged replicas and would race on one checkpoint "
                    "directory; use epoch_end_callback="
                    "mx.callback.do_checkpoint(prefix) with a per-worker "
                    "prefix instead")
            # update_on_kvstore=True semantics: the optimizer runs on the
            # parameter host on every push (reference: pickled-optimizer
            # transport + server-side updater); rank 0's weights initialize
            # the store, every worker starts from the pulled copy.
            kv.set_optimizer(optimizer)
            for name in param_names:
                kv.init(name, self.arg_params[name])
            self._async_pull_params(kv, param_names)

        # -- elastic membership (ISSUE 10): resize the virtual-device dp
        # world mid-run (doc/developer-guide/resilience.md) ----------------
        from .resilience import elastic as elastic_mod

        elastic_co = elastic_mod.ElasticCoordinator.resolve(
            elastic, len(self.ctx))
        elastic_base_ctx = list(self.ctx)  # rank r -> its device, forever
        if elastic_co is not None:
            if mesh is None:
                raise MXNetError(
                    "elastic= needs a multi-device world: give fit a ctx "
                    "list spanning the devices the dp axis may resize over")
            if async_kv or num_workers > 1:
                raise MXNetError(
                    "elastic= resizes the virtual-device dp world; "
                    "multi-process worker membership is the kvstore "
                    "layer's job (membership epochs + leave/join ops)")
            if sharded_checkpoint_dir is None:
                raise MXNetError(
                    "elastic= needs sharded_checkpoint_dir: a resize "
                    "re-shards optimizer state and EF residuals from the "
                    "CRC-manifest checkpoints")
            if elastic_co.full_world_size != int(mesh.shape["dp"]):
                raise MXNetError(
                    f"elastic coordinator world "
                    f"({elastic_co.full_world_size}) does not match the "
                    f"dp axis size ({int(mesh.shape['dp'])})")
            if elastic_co.min_world < 2:
                raise MXNetError(
                    "elastic= needs min_world >= 2: a resize must leave a "
                    "multi-device dp mesh to rebuild (single-device "
                    "training has no axis to reshard onto)")
            # virtual-world identity: hub events/metrics carry the dp
            # world size so post-resize streams are relabeled correctly
            # (restored on exit — the process identity must not keep
            # quoting this run's world after fit returns)
            elastic_prev_world = (telemetry_mod.current_rank(),
                                  telemetry_mod.world_size())
            telemetry_mod.set_world(elastic_prev_world[0],
                                    int(mesh.shape["dp"]))
            telemetry_mod.gauge("elastic_world_size",
                                float(int(mesh.shape["dp"])))

        # device-resident training state (f32 master params). dist_async
        # keeps NO worker-side optimizer state: the server owns it
        # (update-on-kvstore), so a momentum tree here would be dead HBM.
        # the leaves an operator reads in another order than the declared
        # one live on the device in that order from here to the write-back
        # (_stored_order); everything that leaves the loop (arg_params,
        # checkpoints, evaluation) sees the declared shapes
        order = self._state_order(apply_update=not async_kv)
        with telemetry_mod.phase("setup.place_state"):
            params = _reorder({k: jnp.asarray(self.arg_params[k].asnumpy())
                               for k in param_names}, order)
            aux = {k: jnp.asarray(self.aux_params[k].asnumpy())
                   for k in aux_names}
            opt_state = {} if async_kv else optimizer.init_state_tree(params)
            if resume_opt_leaves is not None:
                # restore momentum/moments: re-thread the saved flat leaves
                # through this optimizer's state structure
                flat, treedef = jax.tree_util.tree_flatten(opt_state)
                if len(flat) == len(resume_opt_leaves):
                    saved = jax.tree_util.tree_unflatten(
                        treedef, resume_opt_leaves)
                    opt_state = _reorder(jax.tree_util.tree_map(
                        jnp.asarray, saved), order)
            # how often the mechanism engages: the parameter and
            # optimizer-state leaves that live in another order than the
            # declared one, and their bytes
            moved = [x for k in order for x in jax.tree_util.tree_leaves(
                (params[k], opt_state.get(k))) if x.ndim == len(order[k])]
            self._fit_start.attrs.update(
                state_leaves_relaid=len(moved),
                state_bytes_relaid=sum(x.nbytes for x in moved))
        # One compiled step per bucket key (None = the single-symbol case);
        # all entries share the same live param/opt-state pytrees. The
        # programs live in self._train_fns so precompile() warms the exact
        # entries this loop dispatches; this is just the per-epoch memo.
        train_steps = {}

        # error-feedback comm state: per-device quantization residuals,
        # row-sharded so each device carries only its own error (threaded
        # and donated through the step exactly like the guard state).
        # Under the overlap schedule this is a dict of per-bucket ledgers;
        # either shape is checkpointed with a layout key, and a resumed
        # run only reuses saved residuals that still describe its buckets.
        def _build_comm_state(saved_state, saved_layout):
            """(cstate, layout_key) for the CURRENT mesh/plan: fresh EF
            residual ledgers, or the saved ones when their layout key and
            shapes still describe this world's buckets. Checkpoint resume
            and elastic resize share this decision — a changed axis size
            changes the layout key, so stale residuals (rows laid out for
            the old world) are dropped safely instead of cross-injected."""
            if comm_spec is None or not comm_spec.error_feedback:
                return None, None
            ndev = int(mesh.shape["dp"])
            if overlap_plan is not None:
                resid = comm_mod.init_overlap_residuals(overlap_plan)
                layout_key = overlap_plan.layout_key()
                if saved_state is not None:
                    if saved_layout == layout_key and \
                            comm_mod.residuals_match_plan(saved_state,
                                                          overlap_plan):
                        resid = {k: jnp.asarray(np.asarray(v))
                                 for k, v in saved_state.items()}
                        logger.info("resumed %d per-bucket EF residual "
                                    "ledger(s)", len(resid))
                    else:
                        logger.info(
                            "EF residuals dropped on resume: bucket plan "
                            "changed (%s -> %s); starting a fresh ledger",
                            saved_layout, layout_key)
            else:
                resid = optimizer.init_comm_residual(
                    params, comm_spec, ndev)
                layout_key = comm_mod.fused_layout_key(
                    comm_mod.flat_size(params), comm_spec, ndev)
                if saved_state is not None:
                    saved = saved_state.get("__fused__")
                    if saved_layout == layout_key and \
                            saved is not None and \
                            tuple(saved.shape) == tuple(resid.shape):
                        resid = jnp.asarray(np.asarray(saved))
                        logger.info("resumed fused EF residual")
                    else:
                        logger.info(
                            "EF residual dropped on resume: layout changed "
                            "(%s -> %s)", saved_layout, layout_key)
            return {"resid": jax.device_put(  # mxlint: disable=MX805 - resume-path restore of the comm layer's own EF residual, back onto the plan's dp sharding
                resid, NamedSharding(mesh, P("dp")))}, layout_key

        cstate, resid_layout_key = _build_comm_state(resume_comm_state,
                                                     resume_comm_layout)

        # -- training health (ISSUE 14): in-jit per-layer stats + the
        # streaming anomaly monitor consuming them as a hub sink ----------
        if health_cfg is not None and async_kv:
            logger.info("health= ignored with kvstore='dist_async': the "
                        "worker step carries grads, not updates — the "
                        "update:weight ratio has no in-step meaning")
            health_cfg = None
        health_groups = None
        hstate = None
        hmon = None
        if health_cfg is not None:
            health_groups = telemetry_mod.health.layer_groups(param_names)
            hstate = telemetry_mod.health.init_device_stats(health_groups)
            hmon = telemetry_mod.HealthMonitor(health_cfg).attach()
            self.health_monitor = hmon
            logger.info("health: per-layer stats in-jit over %d layer(s) "
                        "(%r)", len(health_groups), health_cfg)

        # -- fleet controller (ISSUE 12): the policy loop closing the
        # telemetry -> actuation gap (doc/developer-guide/resilience.md,
        # "Fleet controller"). Membership levers actuate through the
        # elastic coordinator above; tier changes are staged by the
        # controller and applied by this loop via _apply_retier.
        from .resilience import controller as fleetctl_mod

        fleet_ctl = fleetctl_mod.FleetController.resolve(controller)
        if fleet_ctl is not None:
            ndev_now = int(mesh.shape["dp"]) if mesh is not None else 1
            fleet_ctl.bind(
                coordinator=elastic_co,
                model_key=str(self._fingerprint_for_bucket(None)),
                world_size=ndev_now,
                comm_mode=comm_spec.mode if comm_spec is not None
                else "none",
                can_retier=mesh is not None and not async_kv,
                fp32_wire_bytes=comm_mod.fp32_allreduce_wire_bytes(
                    comm_mod.flat_size(params), ndev_now)
                if mesh is not None else 0.0,
                health=hmon,
                ckpt_every=(ckpt_every if sharded_checkpoint_dir is not None
                            else None),
                logger=logger)
            logger.info("controller: %s (%r)", fleet_ctl.state,
                        fleet_ctl.cfg)

        # -- resilience wiring (all of it no-op when guards are off and no
        # checkpoint dir is given; the unguarded hot path is unchanged) ----
        gstate = None
        watchdog = None
        if guard_cfg is not None:
            gstate = guards_mod.init_guard_state(guard_cfg, scale=resume_scale)
            self.guard_stats = {"skipped_steps": 0, "step_retries": 0,
                                "loss_scale": float(guard_cfg.init_scale
                                                    if resume_scale is None
                                                    else resume_scale)}
            if guard_cfg.watchdog_deadline:
                watchdog = guards_mod.StepWatchdog(guard_cfg.watchdog_deadline)
        preempt_handler = None
        if sharded_checkpoint_dir is not None or guard_cfg is not None:
            preempt_handler = preempt_mod.PreemptionHandler.install()

        # Feed/compute overlap: batch extraction + async device transfer run
        # on a background thread (double-buffered), so an io-fed epoch costs
        # max(feed, compute) per step, not the sum (see _AsyncDeviceFeed).
        def _extract_batch(batch):
            arrays = {}
            for name, arr in zip(getattr(batch, "data_names", data_names),
                                 batch.data):
                arrays[name] = arr.data
            for name, arr in zip(getattr(batch, "label_names", label_names),
                                 batch.label):
                arrays[name] = arr.data
            if pad_policy is not None:
                # fold tail shapes back into the training shape ON THE FEED
                # THREAD (before the async device transfer): short batches
                # pad up by repeating the last row, iterator wrap-around
                # rows count as padding — the step's validity mask excludes
                # both from loss and metric
                rows = None
                for v in arrays.values():
                    shape = getattr(v, "shape", None)
                    if shape:
                        rows = int(shape[0])
                        break
                target = pad_policy.round_rows(rows, batch_size)
                arrays, num_valid = pad_policy.pad_arrays(
                    arrays, target, pad=getattr(batch, "pad", 0) or 0)
                arrays["__num_valid__"] = np.int32(num_valid)
            return arrays

        def _make_place_batch(mesh_):
            """Batch placement bound to ONE mesh; an elastic resize swaps
            in a fresh closure for the new mesh (a captured sharding
            would silently keep feeding the dead world — the staleness
            class mxlint MX310 flags)."""
            if mesh_ is None:
                _feed_dev = self.ctx[0].jax_device

                def _pb(arrays):
                    return {k: _to_dev(v, _feed_dev)
                            for k, v in arrays.items()}
            else:
                _feed_sh = NamedSharding(mesh_, P("dp"))
                _feed_repl = NamedSharding(mesh_, P())

                def _pb(arrays):
                    # scalars (the pad-policy valid count) replicate; real
                    # batch arrays shard on dp
                    return {k: _place(v, _feed_sh if np.ndim(v)
                                      else _feed_repl)
                            for k, v in arrays.items()}
            return _pb

        _place_batch = _make_place_batch(mesh)

        feed_depth = int(os.environ.get("MXTPU_FEED_PREFETCH", "2"))

        # -- telemetry wiring (tl None = no timeline, no per-step sync; the
        # telemetry.phase() spans of the loop are on either way;
        # doc/developer-guide/telemetry.md) ---------------------------------
        # OOM preflight (ISSUE 9): with a budget configured
        # (MXNET_TPU_HBM_BYTES or the backend's bytes_limit), reject an
        # over-budget configuration NOW — ranked byte report naming the
        # offending arrays/programs — instead of OOMing mid-epoch. Runs
        # before any telemetry state is attached so a raise leaks nothing.
        hbm_budget = telemetry_mod.memory.hbm_budget()
        if hbm_budget:
            plan_label, plan = telemetry_mod.memory.largest_plan(
                (f"train_step:{self._fingerprint_for_bucket(None)}",))
            entries = telemetry_mod.memory.preflight_entries(
                params, opt_state, aux,
                resid=None if cstate is None else cstate["resid"],
                ndev=int(mesh.shape["dp"]) if mesh is not None else 1,
                plan_label=plan_label, plan=plan)
            telemetry_mod.memory.preflight(entries, hbm_budget,
                                           what="fit", logger=logger)

        tl = None
        mfu_acct = None
        tel_sink = None
        mem_prev = None
        if tcfg is not None:
            if tcfg.timeline:
                tl = telemetry_mod.StepTimeline()
                self.telemetry = tl
            if tcfg.mfu:
                mfu_acct = telemetry_mod.MFUAccountant(
                    num_devices=int(mesh.shape["dp"]) if mesh is not None
                    else 1)
            if tcfg.jsonl:
                tel_sink = telemetry_mod.hub().add_sink(
                    telemetry_mod.JsonlWriter(tcfg.jsonl))
            if tcfg.memory:
                # live-array ledger + phase-boundary watermark sampler +
                # epoch leak detector (telemetry/memory.py) — host-side
                # bookkeeping only, so jit cache keys are untouched and
                # the armed zero-recompile epoch stays green
                mem_prev = telemetry_mod.track_arrays(True)
                telemetry_mod.memory.reset_leak_tracker()
                telemetry_mod.memory.attach_sampler()
        self._active_timeline = tl

        # -- cross-run ledger (ISSUE 20): window anchors for the
        # end-of-run RunRecord. The hub ring outlives one fit (tests run
        # many per process), so distillation is bounded to events after
        # this hub timestamp; comm bytes are recorded as the delta
        # against the registry totals captured here.
        _ledger_t0 = telemetry_mod.hub().now()
        _ledger_tic = time.time()
        _ledger_comm0 = comm_mod.registry().stats()

        # -- device-time profiler (ISSUE 15): one bounded capture window,
        # attributed to layers/kernels through the named-scope metadata ----
        prof_session = None
        profile_badput = 0.0
        if profile_cfg is not None:
            # attribution keys: every compute node of the symbol (the
            # scopes exec_node emits) plus the param-derived layer names
            # (what the health/hub surfaces call a layer)
            prof_layers = {n.name for n in self.symbol._topo()
                           if not n.is_variable}
            prof_layers |= set(telemetry_mod.health.layer_groups(
                param_names))
            prof_session = telemetry_mod.profiling.ProfileSession(
                profile_cfg, layers=prof_layers,
                num_devices=int(mesh.shape["dp"]) if mesh is not None
                else 1,
                mfu_acct=mfu_acct, logger=logger, owner="fit")
            logger.info("profile: %r armed (window opens after warmup on "
                        "a compile-quiet step)", profile_cfg)

        def _ckpt_seconds():
            h = telemetry_mod.hub().snapshot()["histograms"].get(
                "checkpoint_save_seconds")
            return h["sum"] if h else 0.0

        eval_metric = metric_mod.create(eval_metric)
        # Device-resident metric accumulation whenever the metric supports it
        # and nothing needs per-batch host values: the (sum, count) scalars
        # live on device inside the train step and are pulled once per epoch.
        # With a batch_end_callback (e.g. Speedometer reading the metric) we
        # keep the reference's per-batch host update semantics. A pad policy
        # additionally needs the metric to honor the row-validity mask
        # (device_mask_supported); otherwise padded batches fall back to the
        # host metric path with the padded rows sliced off.
        use_device_metric = (eval_metric.device_supported
                             and batch_end_callback is None
                             and (pad_policy is None
                                  or eval_metric.device_mask_supported))
        metric_update = eval_metric.device_update if use_device_metric else None
        num_update = resume_num_update
        epoch = self.begin_epoch

        def _write_back():
            # write state back so callbacks/checkpoints see current values,
            # as host-backed NDArrays (cpu context, each leaf's dtype kept:
            # predict/save work off-mesh, nothing is uploaded again).
            # Every fully addressable leaf joins ONE batched transfer:
            # device_get starts all the device-to-host copies before it
            # waits for any, so their latencies overlap. A leaf that spans
            # other processes' devices (jax.distributed) cannot join and
            # gives this process's rows by itself. All copies are complete
            # on return: the next step donates `params`.
            #
            # A parameter kept in a stored order is fetched and landed as
            # it lies (a plain array: it joins the batch at the batch's
            # speed and takes no room on the chip) and goes back to its
            # declared order on the cpu device, where the transposition is
            # XLA's and runs on every core; as a strided numpy view it
            # would be landed by one thread (a v5e's host: 1.3 s for
            # 1.6 GB against 0.39 s for the plain copy).
            leaves = [params[k] for k in param_names] \
                + [aux[k] for k in aux_names]
            joins = [x.is_fully_addressable for x in leaves]
            with telemetry_mod.phase("fit.epoch.write_back", epoch=epoch,
                                     arrays=len(leaves),
                                     bytes=sum(x.nbytes for x in leaves),
                                     batched=sum(joins)):
                fetched = iter(jax.device_get(
                    [x for x, join in zip(leaves, joins) if join]))
                values = [next(fetched) if join else _host_local(x)
                          for x, join in zip(leaves, joins)]
                # no target: the arrays land UNCOMMITTED, as the
                # initializer's do. predict/score hand them to jit beside a
                # batch committed to ctx, which refuses a committed cpu array
                with jax.default_device(cpu().jax_device):
                    n = len(param_names)
                    landed = jax.device_put(values)
                    declared = _reorder(dict(zip(param_names, landed[:n])),
                                        order, back=True)
                self.arg_params.update(
                    (k, NDArray(v)) for k, v in declared.items())
                self.aux_params.update(
                    (k, NDArray(v)) for k, v in zip(aux_names, landed[n:]))

        def _declared_state():
            """``(params, opt_state)`` in their declared order, for what
            leaves the loop (checkpoints): the live trees themselves where
            nothing is stored otherwise, device transposes of the stored
            leaves where something is."""
            return (_reorder(params, order, back=True),
                    _reorder(opt_state, order, back=True))

        # an operator may have a line to say an epoch about its auxiliary
        # states (OpProp.epoch_record; an expert layer's load): once an
        # epoch, with the write-back landed on the host, one zero-length
        # record a node. What is kept between calls are the landed arrays
        # themselves, read only when the record is made
        recorders = [(n.name, n.op) for n in self.symbol._topo()
                     if not n.is_variable
                     and hasattr(n.op, "epoch_record")] \
            if self.symbol is not None else []

        def _node_aux(name, op):
            return [self.aux_params[f"{name}_{a}"]
                    for a in op.list_auxiliary_states()]

        aux_seen = {name: _node_aux(name, op) for name, op in recorders}

        def _epoch_records():
            for name, op in recorders:
                before, aux_seen[name] = aux_seen[name], _node_aux(name, op)
                span, attrs = op.epoch_record(
                    [a.asnumpy() for a in before],
                    [a.asnumpy() for a in aux_seen[name]])
                with telemetry_mod.phase(span, epoch=epoch, node=name,
                                         **attrs):
                    pass

        def _guard_meta():
            if guard_cfg is None:
                return {}
            return {"loss_scale": float(np.asarray(_host_local(
                gstate["scale"])))}

        def _resume_meta(batches_done):
            """Step-granular resume meta (armed runs only): the data
            iterator's position in the epoch plus the generator's key
            words at this step boundary — together with ``num_update``
            they make a resumed run bitwise-equal to one that never
            stopped."""
            if ckpt_every is None:
                return {}
            return {"batches_done": int(batches_done),
                    "rng_state": random_mod.get_state()}

        def _comm_ckpt():
            """(comm_state, meta) for save_sharded: the live EF residual
            ledger(s) plus the layout key resume validates against."""
            if cstate is None:
                return None, {}
            r = cstate["resid"]
            state = dict(r) if isinstance(r, dict) else {"__fused__": r}
            return state, {"comm_layout": resid_layout_key}

        def _preempt_flush():
            """SIGTERM landed: flush the live state as checkpoint ``epoch``
            (meta epoch = the in-progress epoch, which the relaunch redoes
            from its start — epoch-granular resume, same as the reference's
            per-epoch do_checkpoint) and stop via TrainingPreempted."""
            nonlocal params
            if stale_sync:
                # drain the pipelined push first: a round may be in flight
                # one step behind compute, and the checkpoint must not save
                # round-stale weights (push_pull_stale's contract; a drain
                # with nothing in flight is a plain pull)
                pulled = kv.flush_stale(param_names)
                params = {k: jnp.asarray(pulled[k]) for k in param_names}
            if sharded_checkpoint_dir is not None:
                # flush points sit at step boundaries, where the params
                # pytree always holds weights (the async path re-pulls them
                # right after every step), so the live state is consistent.
                # Armed step-granular runs flush under the num_update step
                # id with the full resume meta (batches_done + RNG), so
                # the relaunch resumes mid-epoch instead of redoing it;
                # any queued async snapshot drains first so the flush is
                # the newest step on disk.
                if ckpt_writer is not None:
                    ckpt_writer.flush()
                comm_state, comm_meta = _comm_ckpt()
                step_id = num_update if ckpt_every is not None else epoch
                declared = _declared_state()
                ckpt_plane_mod.save_now(
                    sharded_checkpoint_dir, step_id, declared[0], aux=aux,
                    symbol=self.symbol, opt_state=declared[1],
                    comm_state=comm_state,
                    extra_meta={"epoch": epoch, "num_update": num_update,
                                "preempted": True, **_resume_meta(nbatch),
                                **_guard_meta(), **comm_meta},
                    keep=ckpt_writer.keep_last_k
                    if ckpt_writer is not None else None)
                logger.info("preemption: flushed checkpoint step %d "
                            "(epoch %d, %d updates)", step_id, epoch,
                            num_update)
            # black box alongside the checkpoint: the last K steps +
            # incidents that led into the preemption
            telemetry_mod.flight.auto_dump("preempt")
            _write_back()
            raise preempt_mod.TrainingPreempted(
                f"training preempted by SIGTERM during epoch {epoch} "
                f"(checkpoint flushed: "
                f"{sharded_checkpoint_dir is not None})",
                step=epoch, epoch=epoch)

        def _state_tail():
            """The step signature's LIVE state tail [gstate][cstate]
            [hstate] — one builder for every trace-time consumer (the
            MFU jaxpr trace, the profiler's HLO harvest), reading the
            loop's current values at call time. The dispatch sites keep
            their unrolled shape (donation-hot path)."""
            tail = () if guard_cfg is None else (gstate,)
            if cstate is not None:
                tail += (cstate,)
            if hstate is not None:
                tail += (hstate,)
            return tail

        resize_badput = 0.0  # seconds of the current epoch lost to resizes

        def _apply_resize(ev):
            """Commit a polled membership change: quiesce -> re-shard from
            the CRC-manifest checkpoint onto the new dp axis -> re-derive
            the wire plans -> AOT re-warm the new axis's programs -> let
            the loop redo the interrupted epoch on the new world. The
            whole downtime lands in the timeline as a coordinator span
            (kind="resize") and in goodput as ``resize`` badput."""
            nonlocal mesh, params, opt_state, aux, gstate, cstate, \
                resid_layout_key, overlap_plan, num_update, _place_batch, \
                hstate, skip_batches
            from .utils import checkpoint as ckpt_mod

            t0 = time.time()
            new_size = ev.world_size
            if batch_size % new_size:
                raise MXNetError(
                    f"elastic resize to {new_size} worker(s) impossible: "
                    f"global batch {batch_size} is not divisible by the "
                    f"new dp axis — pick a batch divisible by every world "
                    f"size the job may shrink to")
            rspan = tl.begin_step(epoch, elastic_co.resizes, kind="resize") \
                if tl is not None else None
            try:
                # quiesce: the in-flight step retires before its world dies
                jax.block_until_ready(jax.tree_util.tree_leaves(params)[:1])
                elastic_co.commit(ev, logger=logger)
                self.ctx = [elastic_base_ctx[r] for r in ev.ranks]
                mesh = self._make_mesh(dist=False)
                # re-shard: T1 first (ISSUE 17) — the freshest snapshot
                # whose holder survived restores from RAM with no disk
                # read; disk (T2, the newest CRC-valid checkpoint) is the
                # fallback when the peer died too. A departed rank's
                # replicas are forgotten first so a rejoin cannot
                # resurrect stale state.
                if ckpt_writer is not None:
                    # queued snapshots become the disk fallback's newest
                    # state; drain before deciding which tier restores
                    ckpt_writer.flush()
                restored = None
                if ckpt_replicas is not None:
                    for r in range(ckpt_replicas.world_size):
                        if r not in ev.ranks:
                            ckpt_replicas.drop_rank(r)
                    restored = ckpt_replicas.restore(alive=ev.ranks)
                if restored is not None:
                    t_r = time.time()
                    repl = NamedSharding(mesh, P())
                    loaded = {k: jax.device_put(np.asarray(v), repl)  # mxlint: disable=MX805 - peer-tier restore replicates onto the new mesh, same contract as load_resharded
                              for k, v in
                              restored.state.get("params", {}).items()}
                    laux = {k: jax.device_put(np.asarray(v), repl)  # mxlint: disable=MX805 - peer-tier restore replicates onto the new mesh, same contract as load_resharded
                            for k, v in
                            restored.state.get("aux", {}).items()}
                    meta = dict(restored.meta)
                    opt_leaves = restored.state.get("opt")
                    comm_saved = restored.state.get("comm")
                    jax.block_until_ready(
                        list(loaded.values()) + list(laux.values()))
                    telemetry_mod.counter("ckpt_peer_restores_total")
                    telemetry_mod.emit(
                        "checkpoint", step=restored.step,
                        seconds=time.time() - t_r, tier="t1")
                    logger.info(
                        "elastic: restored step %d from the in-memory "
                        "peer tier (no disk read)", restored.step)
                else:
                    loaded, laux, _, meta, opt_leaves, comm_saved = \
                        ckpt_mod.load_resharded(sharded_checkpoint_dir,
                                                mesh)
                params = _reorder({k: loaded[k] for k in param_names},
                                  order)
                aux = {k: laux[k] for k in aux_names}
                opt_state = optimizer.init_state_tree(params)
                if opt_leaves is not None:
                    flat, treedef = jax.tree_util.tree_flatten(opt_state)
                    if len(flat) == len(opt_leaves):
                        opt_state = _reorder(jax.tree_util.tree_unflatten(
                            treedef,
                            [jnp.asarray(np.asarray(leaf))
                             for leaf in opt_leaves]), order)
                num_update = int(meta.get("num_update", num_update))
                # step-granular resume (ISSUE 17): a mid-epoch snapshot
                # fast-forwards the redone epoch past the batches it
                # already trained, with the RNG rewound to the boundary
                skip_batches = int(meta.get("batches_done", 0))
                if meta.get("rng_state") is not None:
                    random_mod.set_state(meta["rng_state"])
                if guard_cfg is not None:
                    gstate = guards_mod.init_guard_state(
                        guard_cfg, scale=meta.get("loss_scale"))
                    # the rolled-back on-device skip counter restarts at 0
                    self.guard_stats["skipped_steps"] = 0
                # wire plans re-derive for the new axis; EF residuals
                # survive only if their layout key still matches (an axis
                # change never does — _build_comm_state drops them)
                if overlap_plan is not None:
                    overlap_plan = overlap_plan.replan(int(mesh.shape["dp"]))
                cstate, resid_layout_key = _build_comm_state(
                    comm_saved, meta.get("comm_layout"))
                if health_cfg is not None:
                    # stats are per-step; a fresh zero carry placed on the
                    # NEW mesh is the correct post-resize state
                    hstate = telemetry_mod.health.init_device_stats(
                        health_groups)
                train_steps.clear()
                _place_batch = _make_place_batch(mesh)
                if mfu_acct is not None:
                    mfu_acct.set_num_devices(int(mesh.shape["dp"]))
                # AOT re-warmup through TrackedJit: the new axis's fused
                # step compiles NOW, not on the first post-resize batch;
                # growing back to a previously-seen axis finds the old
                # world's programs still warm (precompile is idempotent
                # per signature) and pays nothing
                self.precompile(
                    data_shapes=data_shapes, label_shapes=label_shapes,
                    eval_metric=eval_metric, guards=guard_cfg,
                    pad_policy=pad_policy,
                    # False (not None): resolve(None) would re-read the
                    # env gates and could resurrect a tier the controller
                    # has since re-tiered away from
                    compression=comm_spec if comm_spec is not None
                    else False,
                    overlap=overlap_cfg if overlap_cfg is not None
                    else False,
                    comm_kernels=kern_cfg if kern_cfg is not None
                    else False,
                    batch_end_callback=batch_end_callback,
                    health=health_cfg if health_cfg is not None else False)
            finally:
                if rspan is not None:
                    rspan.end()
            down = time.time() - t0
            elastic_co.record_downtime(down)
            logger.info(
                "elastic: redoing epoch %d on %d device(s) after %.2fs "
                "resize (ranks %s, checkpoint step %s, %d update(s))",
                epoch, int(mesh.shape["dp"]), down, list(ev.ranks),
                meta.get("step", "?"), num_update)

        def _apply_retier(action):
            """Controller-staged compression re-tier: rebuild the fused
            step's comm path on the new tier through the AOT re-warm
            path. Unlike a resize this touches no params/opt state and
            redoes nothing — the next step dispatches the re-tiered
            warmed program. EF residuals restart at zero (a tier change
            invalidates their layout; dropping accumulated error is the
            safe direction). Transactional: a failure restores the old
            program set, counts against the controller's breaker, and
            training continues un-retiered."""
            nonlocal comm_spec, overlap_cfg, overlap_plan, cstate, \
                resid_layout_key
            old = (comm_spec, overlap_cfg, overlap_plan, cstate,
                   resid_layout_key)
            t0 = time.time()
            try:
                # quiesce: the in-flight step's donated buffers must
                # retire before their program set is swapped out
                jax.block_until_ready(
                    jax.tree_util.tree_leaves(params)[:1])
                mode = action["mode"]
                comm_spec = None if mode == "none" \
                    else comm_mod.CompressionSpec(mode)
                overlap_cfg = None
                overlap_plan = None
                if comm_spec is not None and action.get("bucket_bytes"):
                    overlap_cfg = comm_mod.OverlapConfig(
                        action["bucket_bytes"])
                    overlap_plan = comm_mod.plan_overlap(
                        {k: tuple(params[k].shape) for k in param_names},
                        comm_spec, int(mesh.shape["dp"]),
                        max_bytes=overlap_cfg.bucket_bytes,
                        symbol=self.symbol)
                cstate, resid_layout_key = _build_comm_state(None, None)
                train_steps.clear()
                self.precompile(
                    data_shapes=data_shapes, label_shapes=label_shapes,
                    eval_metric=eval_metric, guards=guard_cfg,
                    pad_policy=pad_policy,
                    compression=comm_spec if comm_spec is not None
                    else False,
                    overlap=overlap_cfg if overlap_cfg is not None
                    else False,
                    comm_kernels=kern_cfg if kern_cfg is not None
                    else False,
                    batch_end_callback=batch_end_callback,
                    health=health_cfg if health_cfg is not None else False)
                fleet_ctl.retier_applied(action, time.time() - t0)
                logger.info(
                    "controller: compression re-tiered to %s%s in %.2fs "
                    "(ratio %s)", mode,
                    f" + overlap cap {overlap_cfg.bucket_bytes}"
                    if overlap_cfg is not None else "",
                    time.time() - t0, action.get("ratio"))
            except Exception as e:
                (comm_spec, overlap_cfg, overlap_plan, cstate,
                 resid_layout_key) = old
                train_steps.clear()
                fleet_ctl.actuation_failed("retier", e, logger=logger)

        # -- async multi-tier checkpoint plane (ISSUE 17) ------------------
        ckpt_writer = None
        ckpt_replicas = None
        skip_batches = resume_batches_done
        ckpt_last_update = -1
        if sharded_checkpoint_dir is not None and ckpt_every is not None:
            ckpt_writer = ckpt_plane_mod.AsyncCheckpointWriter(
                sharded_checkpoint_dir, logger=logger)
            _ckpt_world = elastic_co.world_size if elastic_co is not None \
                else (int(mesh.shape["dp"]) if mesh is not None else 1)
            ckpt_replicas = ckpt_plane_mod.ReplicaStore(_ckpt_world)
            # diagnostic/test handle (mirrors self.health_monitor)
            self.ckpt_replicas = ckpt_replicas
            logger.info(
                "ckpt_async: armed every %d step(s) -> %s (keep %d, "
                "queue %d, world %d)", ckpt_every, sharded_checkpoint_dir,
                ckpt_writer.keep_last_k, ckpt_writer.queue_depth,
                _ckpt_world)

        def _ckpt_tick():
            """Cadence hit at a step boundary: ONE blocking device->host
            copy, then training continues — the writer thread owns the
            durable (T2) write and the peer tier (T1) takes the same
            snapshot. Replication of a rank's shard is suppressed when
            the ``ckpt.replica`` chaos site fires (the mid-replication
            kill of the acceptance test)."""
            comm_state, comm_meta = _comm_ckpt()
            declared = _declared_state()
            snap = ckpt_plane_mod.capture_snapshot(
                num_update, declared[0], aux=aux, opt_state=declared[1],
                comm_state=comm_state,
                meta={"epoch": epoch, "num_update": num_update,
                      **_resume_meta(nbatch), **_guard_meta(), **comm_meta},
                symbol=self.symbol)
            ckpt_writer.submit(snap)
            ckpt_writer.note_step(num_update)
            alive = elastic_co.alive if elastic_co is not None \
                else range(ckpt_replicas.world_size)
            for r in alive:
                if not chaos_mod.fires("ckpt.replica"):
                    ckpt_replicas.replicate(r, snap)
            if kv is not None and hasattr(kv, "push_replica"):
                # dist paths mirror the snapshot over the kvstore wire
                # (the ``replica`` op) so a peer PROCESS can restore it
                try:
                    kv.push_replica(kv.rank, num_update,
                                    {"state": snap.state,
                                     "meta": snap.meta})
                except Exception as e:  # T1 is best-effort, T2 stands
                    logger.warning("ckpt_async: wire replication "
                                   "failed: %s", e)

        if elastic_co is not None:
            from .utils import checkpoint as ckpt_mod

            if ckpt_mod.latest_step(sharded_checkpoint_dir) is None:
                # a first-epoch membership change needs a reshard source:
                # persist the starting state as the floor checkpoint
                comm_state, comm_meta = _comm_ckpt()
                floor_id = num_update if ckpt_every is not None else epoch
                declared = _declared_state()
                ckpt_plane_mod.save_now(
                    sharded_checkpoint_dir, floor_id, declared[0],
                    aux=aux, symbol=self.symbol, opt_state=declared[1],
                    comm_state=comm_state,
                    extra_meta={"epoch": epoch, "num_update": num_update,
                                **_resume_meta(resume_batches_done),
                                **_guard_meta(), **comm_meta})

        try:
          final_epoch = self.num_epoch or 1
          epoch = self.begin_epoch
          epoch_tic = None
          self._fit_start.end()
          while epoch < final_epoch:
            with telemetry_mod.phase("fit.epoch", epoch=epoch):
                # the epoch clock survives an elastic redo: on resize the
                # loop `continue`s without advancing `epoch` or resetting the
                # clock, so the aborted attempt + downtime price into this
                # epoch's wall (and its `resize` badput bucket), never into
                # throughput
                if epoch_tic is None:
                    epoch_tic = time.time()
                tic = epoch_tic
                attempt_tic = time.time()
                resize_ev = None
                compile_snap = compile_mod.registry().snapshot()
                comm_snap = comm_mod.registry().snapshot() \
                    if comm_spec is not None else None
                host_comm_snap = kv.compression_stats() \
                    if async_comm_spec is not None and \
                    hasattr(kv, "compression_stats") else None
                epoch_span_base = len(tl.spans) if tl is not None else 0
                ckpt_base = _ckpt_seconds() if mfu_acct is not None else 0.0
                retries_base = self.guard_stats["step_retries"] \
                    if guard_cfg is not None else 0
                skipped_base = self.guard_stats["skipped_steps"] \
                    if guard_cfg is not None else 0
                eval_metric.reset()
                maccum = self._DeviceMetricAccum(eval_metric)
                nbatch = 0
                with telemetry_mod.phase("fit.epoch.feed_start", epoch=epoch):
                    train_data.reset()
                    if feed_depth > 0:
                        feed = _AsyncDeviceFeed(train_data, _extract_batch,
                                                _place_batch, depth=feed_depth,
                                                snapshot=_snapshot_batch,
                                                epoch=epoch)
                    else:  # MXTPU_FEED_PREFETCH=0: synchronous (debugging)
                        feed = ((b, _place_batch(_extract_batch(b)))
                                for b in train_data)
                feed_src = _feed_waits(feed, epoch, tl)
                try:
                    for batch, batch_arrays in feed_src:
                        if skip_batches > 0:
                            # step-granular resume (ISSUE 17): fast-forward a
                            # resumed/redone epoch past batches it already
                            # trained — consume the feed without dispatching,
                            # without drawing RNG keys and without advancing
                            # num_update, so the first live batch sees exactly
                            # the state the checkpointed run saw
                            skip_batches -= 1
                            nbatch += 1
                            continue
                        if fleet_ctl is not None:
                            # policy tick (synchronous mode), then any staged
                            # actuation that must run on the training thread
                            # (tier re-warm). Evictions/backfills the tick
                            # issues land in the coordinator and surface
                            # through the elastic poll right below.
                            if not fleet_ctl.threaded:
                                fleet_ctl.tick()
                            retier_act = fleet_ctl.take_retier()
                            if retier_act is not None:
                                _apply_retier(retier_act)
                        if elastic_co is not None:
                            # membership poll, once per step: chaos sites,
                            # heartbeat expiry, then any pending change —
                            # a hit aborts the attempt (this epoch redoes on
                            # the new world after the resize below)
                            elastic_co.chaos_poll()
                            elastic_co.check_heartbeats()
                            resize_ev = elastic_co.poll()
                            if resize_ev is not None:
                                break
                        with telemetry_mod.phase("fit.step", epoch=epoch,
                                                 step=nbatch) as step_span:
                            span = tl.begin_step(epoch, nbatch) \
                                if tl is not None else None
                            if preempt_handler is not None and \
                                    preempt_mod.preemption_requested():
                                _preempt_flush()
                            if watchdog is not None:
                                watchdog.check()
                            with telemetry_mod.phase("fit.dispatch",
                                                     epoch=epoch,
                                                     step=nbatch) as part:
                                if span is not None:
                                    # dispatch opens as soon as the batch is in
                                    # hand: program-cache resolution /
                                    # first-step graph build / the one-time
                                    # FLOP trace are launch-side host work, not
                                    # a data stall
                                    span.mark("dispatch", ts=part.start)
                                bkey = getattr(batch, "bucket_key", None)
                                b_dnames = getattr(batch, "data_names",
                                                   data_names)
                                b_lnames = getattr(batch, "label_names",
                                                   label_names)
                                if bkey not in train_steps:
                                    train_steps[bkey] = self._get_train_step(
                                        bkey, b_dnames, b_lnames, optimizer,
                                        mesh,
                                        metric=eval_metric
                                        if use_device_metric else None,
                                        apply_update=not async_kv,
                                        guard_cfg=guard_cfg,
                                        pad_policy=pad_policy,
                                        compression=comm_spec,
                                        overlap_plan=overlap_plan,
                                        comm_kernels=kern_cfg,
                                        health_cfg=health_cfg)
                                train_step = train_steps[bkey]
                                pad_tail = ()
                                if pad_policy is not None:
                                    pad_tail = (
                                        batch_arrays.pop("__num_valid__"),)
                                rng = random_mod.next_key()
                                lr = optimizer._get_lr()
                                optimizer.num_update = num_update
                                if mfu_acct is not None and \
                                        mfu_acct.flops_per_step is None and \
                                        getattr(train_step, "_tracked",
                                                None) is not None:
                                    # abstract-trace the exact program about to
                                    # dispatch (shapes only, pre-donation) for
                                    # the jaxpr FLOP table behind the MFU line
                                    mfu_acct.maybe_trace(
                                        train_step._tracked._jitted,
                                        (params, opt_state, aux,
                                         batch_arrays, rng, jnp.float32(lr),
                                         maccum.state)
                                        + _state_tail() + pad_tail)
                                if prof_session is not None and \
                                        prof_session.pending:
                                    # maybe open the capture window (warmup
                                    # done AND last step compile-quiet); the
                                    # args thunk lets the session harvest this
                                    # exact program's HLO metadata
                                    def _prof_args():
                                        return (params, opt_state, aux,
                                                batch_arrays, rng,
                                                jnp.float32(lr),
                                                maccum.state) \
                                            + _state_tail() + pad_tail
                                    prof_session.before_step(
                                        getattr(train_step, "_tracked", None),
                                        _prof_args,
                                        compile_mod.registry().snapshot()[
                                            "compiles"])
                                if shard_audit_on and \
                                        bkey not in _shard_audited:
                                    _shard_audited.add(bkey)
                                    tj = getattr(train_step, "_tracked", None)
                                    if tj is not None:
                                        # warms the exact program about to
                                        # dispatch (TrackedJit AOT) and audits
                                        # its optimized HLO; raises on MX802
                                        # before the step runs
                                        self._shard_audit_program(
                                            tj,
                                            (params, opt_state, aux,
                                             batch_arrays, rng,
                                             jnp.float32(lr), maccum.state)
                                            + _state_tail() + pad_tail,
                                            mesh=mesh, comm_spec=comm_spec,
                                            overlap_plan=overlap_plan,
                                            flat_elems=comm_mod.flat_size(
                                                params),
                                            logger=logger)
                                # state tail mirrors the step signature:
                                # [gstate][cstate][hstate][valid]
                                hs_tail = () if hstate is None else (hstate,)
                                if guard_cfg is None:
                                    tail = () if cstate is None else (cstate,)
                                    res = train_step(params, opt_state, aux,
                                                     batch_arrays, rng, lr,
                                                     maccum.state, *tail,
                                                     *hs_tail, *pad_tail)
                                else:
                                    batch_arrays = self._chaos_step_sites(
                                        batch_arrays, b_dnames, watchdog)
                                    retries = guard_cfg.max_step_retries
                                    while True:
                                        try:
                                            # the injected raise fires BEFORE
                                            # dispatch, so donated buffers are
                                            # still live on retry
                                            chaos_mod.maybe_raise(
                                                "step.raise",
                                                chaos_mod.TransientStepError)
                                            tail = (gstate,) \
                                                if cstate is None \
                                                else (gstate, cstate)
                                            res = train_step(
                                                params, opt_state, aux,
                                                batch_arrays, rng, lr,
                                                maccum.state, *tail,
                                                *hs_tail, *pad_tail)
                                            break
                                        except chaos_mod.TransientStepError:
                                            if retries <= 0:
                                                # retry budget exhausted: leave
                                                # a black box before failing
                                                # the run
                                                telemetry_mod.flight.auto_dump(
                                                    "guard_trip")
                                                raise
                                            retries -= 1
                                            self.guard_stats[
                                                "step_retries"] += 1
                                            telemetry_mod.counter(
                                                "resilience_step_retries"
                                                "_total")
                                            if span is not None:
                                                span.event("step_retry")
                                    if watchdog is not None:
                                        watchdog.beat()
                            with telemetry_mod.phase("fit.step_host",
                                                     epoch=epoch,
                                                     step=nbatch) as part:
                                if span is not None:
                                    span.mark("device", ts=part.start)
                                    if tcfg.sync:
                                        # exact device phase: wait for the
                                        # step's output buffers (see
                                        # TelemetryConfig.sync)
                                        jax.block_until_ready(res)
                                    # stale-sync: the kvstore slot becomes
                                    # "wire" — it times only the un-hidden tail
                                    # of the PREVIOUS round's push (the hidden
                                    # part lands as an "overlap" sub-span from
                                    # push_pull_stale)
                                    span.mark("wire" if stale_sync
                                              else ("kvstore" if async_kv
                                                    else "host"))
                                params, opt_state, aux, outs, maccum.state = \
                                    res[:5]
                                idx = 5
                                if guard_cfg is not None:
                                    gstate = res[idx]
                                    idx += 1
                                if cstate is not None:
                                    cstate = res[idx]
                                    idx += 1
                                if hstate is not None:
                                    hstate = res[idx]
                                    if nbatch % health_cfg.every == 0:
                                        # pull the tiny stat vectors + emit the
                                        # health event; the monitor's detectors
                                        # run inside the emit, so any
                                        # health_anomaly lands in the flight
                                        # ring BEFORE the guard-skip event that
                                        # closes the story
                                        _, h_finite = telemetry_mod.health \
                                            .observe_device_stats(
                                                health_groups, hstate, epoch,
                                                nbatch)
                                        # only a guard that actually skips gets
                                        # the skip event — with
                                        # skip_nonfinite=False the poisoned
                                        # update was APPLIED, and a post-
                                        # mortem must not read a skip that
                                        # never ran
                                        if guard_cfg is not None and \
                                                guard_cfg.skip_nonfinite and \
                                                not h_finite:
                                            if span is not None:
                                                span.event("guard_skip")
                                            else:
                                                telemetry_mod.emit(
                                                    "step_event",
                                                    span_kind="step",
                                                    epoch=epoch, step=nbatch,
                                                    name="guard_skip")
                                if prof_session is not None and \
                                        prof_session.open:
                                    # window accounting: the K-th step blocks
                                    # on its outputs, stops the trace,
                                    # attributes, publishes; the wall time
                                    # returns as `profile` badput
                                    profile_badput += prof_session.after_step(
                                        res, epoch=epoch)
                                step_finite = True
                                if guard_cfg is not None and (
                                        async_kv or not use_device_metric):
                                    # these paths sync to host right below
                                    # anyway; the in-jit fast path never reads
                                    # this flag
                                    step_finite = bool(
                                        np.asarray(  # mxlint: disable=MX309
                                            _host_local(
                                                gstate["last_finite"])))
                                if async_kv:
                                    if step_finite and stale_sync:
                                        # pipelined push: THIS step's grads go
                                        # to the parameter host on a background
                                        # thread while the next step computes;
                                        # the weights returned are one round
                                        # stale (overlap= on dist_async)
                                        pulled = kv.push_pull_stale(
                                            {name: _host_local(params[name])
                                             for name in param_names})
                                    elif step_finite:
                                        # params slot carries grads
                                        # (apply_update=False): ONE round trip
                                        # applies them on the host (updated on
                                        # arrival) and returns the fresh
                                        # weights — unbounded-staleness async,
                                        # like the reference's dist_async
                                        # worker loop
                                        pulled = kv.push_pull(
                                            {name: _host_local(params[name])
                                             for name in param_names})
                                    elif stale_sync:
                                        # guard tripped: drain the in-flight
                                        # round, drop the bad grads, re-pull
                                        # current weights
                                        pulled = kv.flush_stale(param_names)
                                    else:
                                        # guard tripped: the grads are
                                        # non-finite — do NOT poison the
                                        # parameter host; re-pull the current
                                        # weights instead (the params slot
                                        # holds the bad grads and must be
                                        # replaced either way)
                                        pulled = kv.pull_many(param_names)
                                    params = {k: jnp.asarray(pulled[k])
                                              for k in param_names}
                                if span is not None and async_kv:
                                    span.mark("host")
                                num_update += 1
                                if use_device_metric:
                                    maccum.after_batch(batch.label)
                                elif step_finite:
                                    outs_h = [
                                        _host_local(o)
                                        for o in outs[: len(batch.label)]]
                                    labels_h = batch.label
                                    if pad_policy is not None:
                                        # batch.label holds the UNPADDED rows;
                                        # slice the outputs to the valid prefix
                                        # (wrap-around pad rows excluded too —
                                        # that's the policy's
                                        # metric-correctness contract)
                                        nv = int(labels_h[0].shape[0]) - int(
                                            getattr(batch, "pad", 0) or 0)
                                        outs_h = [o[:nv] for o in outs_h]
                                        # host-metric path: the per-batch pull
                                        # IS the metric contract here (device
                                        # metrics are the sanctioned fast path)
                                        labels_h = [
                                            np.asarray(l.asnumpy()  # mxlint: disable=MX309
                                                       if hasattr(l, "asnumpy")
                                                       else l)[:nv]
                                            for l in labels_h]
                                    eval_metric.update(
                                        labels_h, [NDArray(o) for o in outs_h])
                                nbatch += 1
                                if ckpt_writer is not None and \
                                        num_update % ckpt_every == 0 and \
                                        num_update != ckpt_last_update:
                                    # cadence hit (ISSUE 17): one blocking host
                                    # copy, then the writer thread owns
                                    # durability — the loop is back on the next
                                    # batch immediately. (guard-skipped steps
                                    # leave num_update in place: the dedup
                                    # keeps a skipped batch from re-saving the
                                    # same update)
                                    ckpt_last_update = num_update
                                    _ckpt_tick()
                                if ckpt_writer is not None and \
                                        fleet_ctl is not None:
                                    ckpt_act = fleet_ctl.take_ckpt_cadence()
                                    if ckpt_act is not None:
                                        # controller-staged cadence change:
                                        # host-side counter only, nothing
                                        # recompiles
                                        ckpt_every = max(
                                            1, int(ckpt_act["every"]))
                                        fleet_ctl.ckpt_cadence_applied(
                                            ckpt_act)
                                        logger.info(
                                            "controller: checkpoint cadence "
                                            "-> every %d step(s)", ckpt_every)
                                if batch_end_callback is not None:
                                    p = BatchEndParam(
                                        epoch=epoch, nbatch=nbatch,
                                        eval_metric=eval_metric)
                                    for cb in _as_list(batch_end_callback):
                                        cb(p)
                            if span is not None:
                                span.end()
                        if span is None:
                            # timeline off: the always-on flight recorder
                            # still gets a step mark (identity, timestamp,
                            # duration), so a crash dump shows the last K
                            # steps either way
                            telemetry_mod.flight.note_step(
                                epoch, nbatch - 1, dur_ms=1e3 * (
                                    step_span.end_ts - step_span.start))
                finally:
                    if feed_depth > 0:
                        with telemetry_mod.phase("fit.epoch.feed_close",
                                                 epoch=epoch):
                            feed.close()
                if resize_ev is not None:
                    # elastic resize: quiesce, re-shard, re-plan, re-warm —
                    # then redo this epoch on the new world. Everything the
                    # aborted attempt spent (its steps get redone) plus the
                    # resize downtime is this epoch's `resize` badput.
                    _apply_resize(resize_ev)
                    resize_badput += time.time() - attempt_tic
                    continue
                if stale_sync:
                    # drain the pipeline at the epoch boundary: the last step's
                    # push must land before callbacks/checkpoints read weights
                    pulled = kv.flush_stale(param_names)
                    params = {k: jnp.asarray(pulled[k]) for k in param_names}
                with telemetry_mod.phase("fit.epoch.drain", epoch=epoch):
                    if use_device_metric:
                        maccum.finish()
                    # stop the epoch clock only once the last step's buffers
                    # are ready — a returned dispatch is not a finished step
                    # (the un-barriered-timing footgun, mxlint MX306)
                    jax.block_until_ready(
                        jax.tree_util.tree_leaves(params)[:1])
                if prof_session is not None and prof_session.open:
                    # epoch ended inside the window: the device work above has
                    # retired, so close with what was captured rather than
                    # leaking an open trace into the next epoch
                    profile_badput += prof_session.close(epoch=epoch)
                with telemetry_mod.phase("fit.epoch.metric_pull",
                                         epoch=epoch):
                    name, value = eval_metric.get()
                    logger.info("Epoch[%d] Train-%s=%f", epoch, name, value)
                    logger.info("Epoch[%d] Time cost=%.3f", epoch,
                                time.time() - tic)
                cdiff = compile_mod.registry().snapshot()
                if cdiff["compiles"] > compile_snap["compiles"]:
                    # compile activity this epoch (expected in epoch 1 / on
                    # a new bucket; anything later is shape drift — see
                    # RecompileTracker): programs, seconds, cache traffic
                    logger.info(
                        "Epoch[%d] Compile: %d XLA compile(s), %.2fs "
                        "(jit hits=%d misses=%d, persistent-cache hits=%d, "
                        "saved=%.2fs)", epoch,
                        cdiff["compiles"] - compile_snap["compiles"],
                        cdiff["compile_seconds"]
                        - compile_snap["compile_seconds"],
                        cdiff["hits"] - compile_snap["hits"],
                        cdiff["misses"] - compile_snap["misses"],
                        cdiff["persistent_cache_hits"]
                        - compile_snap["persistent_cache_hits"],
                        cdiff["persistent_cache_saved_seconds"]
                        - compile_snap["persistent_cache_saved_seconds"])
                if comm_snap is not None:
                    cdelta = comm_mod.registry().snapshot()
                    steps_d = cdelta["steps"] - comm_snap["steps"]
                    if steps_d:
                        wire_d = cdelta["wire_bytes"] - comm_snap["wire_bytes"]
                        fp32_d = (cdelta["fp32_wire_bytes"]
                                  - comm_snap["fp32_wire_bytes"])
                        logger.info(
                            "Epoch[%d] Comm: %d sync steps, %.2f MB on the "
                            "wire (%s; fp32 would be %.2f MB, %.1fx)", epoch,
                            steps_d, wire_d / 1e6, comm_spec.mode,
                            fp32_d / 1e6,
                            fp32_d / wire_d if wire_d else float("inf"))
                if host_comm_snap is not None:
                    hs = kv.compression_stats()
                    sent_d = hs["bytes_encoded"] \
                        - host_comm_snap["bytes_encoded"]
                    raw_d = hs["bytes_raw"] - host_comm_snap["bytes_raw"]
                    if sent_d:
                        logger.info(
                            "Epoch[%d] Comm: %.2f MB pushed to the parameter "
                            "host (%s; fp32 would be %.2f MB, %.1fx)", epoch,
                            sent_d / 1e6, async_comm_spec.mode, raw_d / 1e6,
                            raw_d / sent_d)
                if stale_sync and tl is not None:
                    # overlap accounting (needs the sync timeline): wire
                    # phase = the blocked tail, overlap subs = what the
                    # pipeline hid
                    spans_e = tl.spans[epoch_span_base:]
                    compute_s = sum(d for s in spans_e
                                    for n, _, d in s.phases() if n == "device")
                    tail_s = sum(d for s in spans_e
                                 for n, _, d in s.phases() if n == "wire")
                    hidden_s = sum(d for s in spans_e
                                   for n, _, d in s.subs if n == "overlap")
                    # step = the schedule-controlled time (device compute +
                    # blocking wire tail) — NOT the whole span: data_wait/
                    # dispatch/host stalls are not the pipeline's doing and
                    # would read as negative efficiency on a slow
                    # dataloader
                    eff = comm_mod.overlap_efficiency(
                        compute_s + tail_s, compute_s, tail_s + hidden_s)
                    telemetry_mod.gauge("comm_overlap_efficiency", eff)
                    logger.info(
                        "Epoch[%d] Overlap: %.2fs on the wire (%.2fs hidden "
                        "under compute, %.2fs blocking tail), efficiency=%.2f",
                        epoch, tail_s + hidden_s, hidden_s, tail_s, eff)
                if guard_cfg is not None:
                    self.guard_stats["skipped_steps"] = int(np.asarray(
                        _host_local(gstate["skipped"])))
                    self.guard_stats["loss_scale"] = float(np.asarray(
                        _host_local(gstate["scale"])))
                    skipped_delta = self.guard_stats["skipped_steps"] \
                        - skipped_base
                    if skipped_delta > 0:
                        telemetry_mod.counter("resilience_skipped_steps_total",
                                              skipped_delta)
                    telemetry_mod.gauge("loss_scale",
                                        self.guard_stats["loss_scale"])
                    if self.guard_stats["skipped_steps"] or \
                            self.guard_stats["step_retries"]:
                        logger.info(
                            "Epoch[%d] Guard: skipped_steps=%d "
                            "step_retries=%d loss_scale=%g", epoch,
                            self.guard_stats["skipped_steps"],
                            self.guard_stats["step_retries"],
                            self.guard_stats["loss_scale"])

                if sharded_checkpoint_dir is not None:
                    with telemetry_mod.phase("fit.epoch.checkpoint",
                                             epoch=epoch):
                        if ckpt_writer is not None:
                            # drain first: a queued cadence snapshot may share
                            # this num_update's step id, and two writers must
                            # never race one .tmp.<step> dir
                            ckpt_writer.flush()
                        comm_state, comm_meta = _comm_ckpt()
                        # armed runs keep ONE step-id namespace (num_update)
                        # for cadence and epoch-end saves; unarmed runs keep
                        # the legacy epoch-granular ids. batches_done=0: the
                        # resumed run starts the NEXT epoch from its top.
                        step_id = num_update if ckpt_every is not None \
                            else epoch + 1
                        declared = _declared_state()
                        ckpt_plane_mod.save_now(
                            sharded_checkpoint_dir, step_id, declared[0],
                            aux=aux, symbol=self.symbol,
                            opt_state=declared[1],
                            comm_state=comm_state,
                            extra_meta={"epoch": epoch + 1,
                                        "num_update": num_update,
                                        **_resume_meta(0), **_guard_meta(),
                                        **comm_meta},
                            keep=ckpt_writer.keep_last_k
                            if ckpt_writer is not None else None)

                if mfu_acct is not None and nbatch:
                    spans_e = tl.spans[epoch_span_base:] \
                        if tl is not None else []
                    data_wait = sum(d for s in spans_e
                                    for n, _, d in s.phases()
                                    if n == "data_wait")
                    mfu_acct.epoch_report(
                        epoch, nbatch, time.time() - tic,
                        compile_seconds=cdiff["compile_seconds"]
                        - compile_snap["compile_seconds"],
                        data_wait_seconds=data_wait,
                        skipped_steps=(self.guard_stats["skipped_steps"]
                                       - skipped_base)
                        if guard_cfg is not None else 0,
                        step_retries=(self.guard_stats["step_retries"]
                                      - retries_base)
                        if guard_cfg is not None else 0,
                        checkpoint_seconds=_ckpt_seconds() - ckpt_base,
                        resize_seconds=resize_badput,
                        profile_seconds=profile_badput,
                        logger=logger)

                _write_back()
                _epoch_records()

                if mem_prev is not None:
                    # close the epoch's watermark window: emits the
                    # memory_watermark event and runs the epoch-over-epoch
                    # leak detector (telemetry/memory.py)
                    telemetry_mod.memory.epoch_mark(epoch, logger=logger)

                if eval_data is not None:
                    with telemetry_mod.phase("fit.epoch.eval", epoch=epoch):
                        eval_metric.reset()
                        eval_iter = _init_iter(
                            eval_data[0], eval_data[1], batch_size,
                            is_train=False) \
                            if isinstance(eval_data, tuple) else eval_data
                        # the evaluation program is the declared graph:
                        # it gets the leaves in their declared order
                        # (device transposes, dropped after the pass)
                        self._eval(eval_iter, eval_metric,
                                   _reorder(params, order, back=True), aux,
                                   data_names, label_names)
                        name, value = eval_metric.get()
                        logger.info("Epoch[%d] Validation-%s=%f", epoch, name,
                                    value)

                if epoch_end_callback is not None:
                    with telemetry_mod.phase("fit.epoch.callback",
                                             epoch=epoch):
                        if preempt_handler is not None and \
                                preempt_mod.preemption_requested():
                            # don't start callbacks on a dead clock
                            _preempt_flush()
                        for cb in _as_list(epoch_end_callback):
                            cb(epoch, self.symbol, self.arg_params,
                               self.aux_params)
                epoch_tic = None
                resize_badput = 0.0
                profile_badput = 0.0
                epoch += 1
        finally:
            if ckpt_writer is not None:
                # drain queued snapshots so the last cadence hit is
                # durable, then stop mx-ckpt-writer
                ckpt_writer.close()
            if watchdog is not None:
                watchdog.stop()
            if preempt_handler is not None:
                preempt_mod.PreemptionHandler.uninstall()
            if fleet_ctl is not None:
                fleet_ctl.unbind()
            if hmon is not None:
                hmon.detach()
            if prof_session is not None:
                # an exception mid-window must not leave the process-global
                # jax profiler running; a closed session's close() is a
                # no-op
                prof_session.close()
                self.profile_report = prof_session.report
            if elastic_co is not None:
                telemetry_mod.set_world(*elastic_prev_world)
            # a mid-step exception (preemption, retry exhaustion) can leave
            # an un-ended span in the thread-local slot; later phase()
            # calls must not attach to it, and score()/eval after this fit
            # must not inherit the finished timeline
            telemetry_mod.clear_current_span()
            self._active_timeline = None
            if tel_sink is not None:
                telemetry_mod.hub().remove_sink(tel_sink)
                tel_sink.close()
            if mem_prev is not None:
                telemetry_mod.memory.detach_sampler()
                telemetry_mod.track_arrays(mem_prev)
            # -- cross-run ledger (ISSUE 20): distill this run into one
            # persistent RunRecord. comm_spec reflects the FINAL tier
            # (_apply_retier rebinds it via nonlocal), so the knob vector
            # records what the run actually ended on. Best-effort: the
            # ledger must never mask the run's own outcome.
            try:
                _lc = comm_spec if comm_spec is not None else async_comm_spec
                try:
                    _fused = bool(optimizer._fused_active())
                except Exception:
                    _fused = False
                telemetry_mod.ledger.record_run(
                    "fit",
                    fingerprint=str(self._fingerprint_for_bucket(None)),
                    world_size=(int(mesh.shape["dp"])
                                if mesh is not None else 1),
                    knobs={
                        "compression": _lc.mode if _lc is not None else "none",
                        "overlap_bytes": (overlap_cfg.bucket_bytes
                                          if overlap_cfg is not None else None),
                        "comm_kernels": kern_cfg is not None,
                        "fused_adam": _fused,
                        "pad_policy": (pad_policy.mode
                                       if pad_policy is not None else None),
                        "health": health_cfg is not None,
                        "profile": profile_cfg is not None,
                        "guards": guard_cfg is not None,
                        "ckpt_every": ckpt_every,
                    },
                    completed=sys.exc_info()[0] is None,
                    since_ts=_ledger_t0,
                    comm_start=_ledger_comm0,
                    wall_seconds=time.time() - _ledger_tic,
                    logger=logger)
            except Exception as e:
                logger.warning("telemetry ledger: run record failed: %s", e)
        return self

    # -- AOT warmup -----------------------------------------------------------
    def precompile(self, data_shapes=None, label_shapes=None, *, data=None,
                   eval_metric="accuracy", kvstore="local", guards=None,
                   pad_policy=None, compression=None, overlap=None,
                   comm_kernels=None, batch_end_callback=None,
                   health=None, parallel=True, shard_audit=None):
        """AOT warmup: compile every fused train program ``fit`` would need
        BEFORE training, via ``.lower().compile()`` — so step 1 of each
        shape dispatches a ready executable instead of stalling on XLA
        (minutes per program on a real pod). Programs compile in parallel
        threads (XLA releases the GIL), and land in the same instance cache
        ``fit`` consults, keyed by the exact program configuration.

        Shapes: pass ``data_shapes``/``label_shapes`` dicts (input name ->
        full batch shape, optionally ``(shape, dtype)``), or ``data=`` a
        DataIter to read them off ``provide_data``/``provide_label`` — a
        ``BucketSentenceIter`` warms one program per non-empty bucket.
        ``eval_metric``/``guards``/``pad_policy``/``batch_end_callback``
        must match the eventual ``fit`` call — each changes the compiled
        program (a batch callback forces the per-batch host metric path,
        un-fusing the device metric). ``fit`` warns if a mismatch orphans
        the warmed programs.

        Returns ``{"programs", "wall_seconds", "labels"}``. With the
        persistent compilation cache (utils/compile.py) the first process
        pays XLA once, every later precompile deserializes from disk.
        """
        if isinstance(kvstore, str) and "dist" in kvstore:
            raise MXNetError(
                "precompile: multi-process kvstore strategies must warm up "
                "inside the launched job (the mesh spans processes); call "
                "precompile there, or rely on the persistent cache")
        programs = []
        if data is not None:
            if hasattr(data, "bucket_shapes"):
                programs = [(bk, dict(d), dict(l))
                            for bk, d, l in data.bucket_shapes()]
            else:
                programs = [(None, dict(data.provide_data),
                             dict(data.provide_label))]
        elif data_shapes:
            programs = [(None, dict(data_shapes), dict(label_shapes or {}))]
        if not programs:
            raise MXNetError(
                "precompile: pass data_shapes (+label_shapes) or "
                "data=<DataIter>")

        def _split(spec):
            # shape, or (shape, dtype)
            if (isinstance(spec, tuple) and len(spec) == 2
                    and isinstance(spec[0], (tuple, list))):
                return tuple(spec[0]), np.dtype(spec[1])
            return tuple(spec), np.dtype(np.float32)

        guard_cfg = guards_mod.GuardConfig.resolve(guards)
        pad_policy = compile_mod.PadPolicy.resolve(pad_policy)
        health_cfg = telemetry_mod.HealthConfig.resolve(health)
        from . import comm as comm_mod

        comm_spec = comm_mod.CompressionSpec.resolve(compression)
        overlap_cfg = comm_mod.OverlapConfig.resolve(overlap)
        kern_cfg = comm_mod.CommKernelConfig.resolve(comm_kernels)
        metric = metric_mod.create(eval_metric)
        # same fusion decision as fit(): a batch callback needs per-batch
        # host metric values, so the metric stays out of the step program
        use_device_metric = (metric.device_supported
                             and batch_end_callback is None
                             and (pad_policy is None
                                  or metric.device_mask_supported))

        if data is not None:
            init_shapes = {**dict(data.provide_data),
                           **dict(data.provide_label)}
        else:
            init_shapes = {k: _split(v)[0]
                           for k, v in {**programs[0][1],
                                        **programs[0][2]}.items()}
        param_names, aux_names = self._init_params(init_shapes)
        first_shape = _split(next(iter(programs[0][1].values())))[0]
        batch_size = int(first_shape[0])
        mesh = self._make_mesh(dist=False)
        if mesh is None:
            comm_spec = None  # matches fit(): no mesh, no wire, no comm
        overlap_plan = None
        if comm_spec is not None and overlap_cfg is not None:
            # the EXACT plan fit() will build — same symbol order, shapes,
            # cap — so the warmed program is the one fit dispatches
            overlap_plan = comm_mod.plan_overlap(
                {k: tuple(self.arg_params[k].shape) for k in param_names},
                comm_spec, int(mesh.shape["dp"]),
                max_bytes=overlap_cfg.bucket_bytes, symbol=self.symbol)
        optimizer = self._resolve_optimizer(param_names, batch_size)

        def _sds(shape, dtype, sharded=False):
            if mesh is None:
                return jax.ShapeDtypeStruct(shape, dtype)
            sh = NamedSharding(mesh, P("dp") if sharded else P())
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

        order = self._state_order()
        params_s = {k: _sds(tuple(self.arg_params[k].shape[a]
                                  for a in order.get(
                                      k, range(self.arg_params[k].ndim))),
                            self.arg_params[k].dtype) for k in param_names}
        aux_s = {k: _sds(tuple(self.aux_params[k].shape),
                         self.aux_params[k].dtype) for k in aux_names}
        opt_state_s = jax.eval_shape(optimizer.init_state_tree, params_s)
        if mesh is not None:
            opt_state_s = jax.tree_util.tree_map(
                lambda s: _sds(tuple(s.shape), s.dtype), opt_state_s)
        rng_s = _sds((2,), np.dtype(np.uint32))
        lr_s = _sds((), np.dtype(np.float32))
        mstate = metric.device_init()
        mstate_s = jax.tree_util.tree_map(
            lambda x: _sds(tuple(x.shape), np.dtype(x.dtype)), mstate)

        jobs = []
        ef_resid_struct = None  # the EF residual shape the warmup lowers for
        for bkey, d, l in programs:
            data_names_p = list(d)
            label_names_p = list(l)
            step = self._get_train_step(
                bkey, data_names_p, label_names_p, optimizer, mesh,
                metric=metric if use_device_metric else None,
                apply_update=True, guard_cfg=guard_cfg,
                pad_policy=pad_policy, compression=comm_spec,
                overlap_plan=overlap_plan, comm_kernels=kern_cfg,
                health_cfg=health_cfg)
            batch_s = {}
            for name, spec in {**d, **l}.items():
                shape, dtype = _split(spec)
                batch_s[name] = _sds(shape, dtype, sharded=True)
            args = (params_s, opt_state_s, aux_s, batch_s, rng_s, lr_s,
                    mstate_s)
            if guard_cfg is not None:
                args += (guards_mod.init_guard_state(guard_cfg),)
            if comm_spec is not None and comm_spec.error_feedback:
                ndev = int(mesh.shape["dp"])
                if overlap_plan is not None:
                    resid_s = {name: _sds((ndev, lp), np.dtype(np.float32),
                                          sharded=True)
                               for name, lp
                               in overlap_plan.padded_sizes().items()}
                    args += ({"resid": resid_s},)
                else:
                    Lp = comm_mod.padded_flat_size(
                        sum(int(np.prod(self.arg_params[k].shape))
                            for k in param_names), comm_spec, ndev)
                    args += ({"resid": _sds((ndev, Lp),
                                            np.dtype(np.float32),
                                            sharded=True)},)
                ef_resid_struct = args[-1]["resid"]
            if health_cfg is not None:
                groups = telemetry_mod.health.layer_groups(param_names)
                hs = telemetry_mod.health.init_device_stats(groups)
                args += (jax.tree_util.tree_map(
                    lambda x: _sds(tuple(x.shape), np.dtype(x.dtype)), hs),)
            if pad_policy is not None:
                args += (_sds((), np.dtype(np.int32)),)
            jobs.append((step._tracked, args))

        def _compile(tj, args):
            # the lowering and the XLA compile, or the persistent cache's read
            with telemetry_mod.phase("setup.compile", label=tj.label):
                tj.precompile(*args)

        t0 = time.time()
        if parallel and len(jobs) > 1:
            import concurrent.futures as cf

            workers = min(len(jobs), int(os.environ.get(
                "MXNET_TPU_PRECOMPILE_THREADS", "4")))
            with cf.ThreadPoolExecutor(max_workers=workers,
                                       thread_name_prefix="mx-precompile") \
                    as pool:
                futures = [pool.submit(_compile, tj, args)
                           for tj, args in jobs]
                for f in futures:
                    f.result()
        else:
            for tj, args in jobs:
                _compile(tj, args)
        wall = time.time() - t0
        logging.info("precompile: %d program(s) ready in %.2fs", len(jobs),
                     wall)
        # OOM preflight over the EXACT warmed programs: every job just
        # registered its memory plan, so the check uses real temp/output
        # bytes — reject an over-budget configuration here, before fit
        # dispatches a single step (ISSUE 9)
        hbm_budget = telemetry_mod.memory.hbm_budget()
        if hbm_budget:
            plan_label, plan = telemetry_mod.memory.largest_plan(
                labels=[tj.label for tj, _ in jobs])
            entries = telemetry_mod.memory.preflight_entries(
                params_s, opt_state_s, aux_s,
                resid=ef_resid_struct,
                ndev=int(mesh.shape["dp"]) if mesh is not None else 1,
                plan_label=plan_label, plan=plan)
            telemetry_mod.memory.preflight(entries, hbm_budget,
                                           what="precompile")
        # opt-in shard audit over the EXACT warmed executables (ISSUE 16):
        # shard_audit=True / MXNET_TPU_SHARD_AUDIT raises on MX802 drift;
        # shard_audit="report" collects findings without raising (the
        # --shardcheck CLI path)
        from .analysis.sharding import shard_audit_enabled
        report_only = shard_audit == "report"
        shard_reports = []
        if (report_only or shard_audit_enabled(shard_audit)) \
                and mesh is not None:
            flat_elems = sum(int(np.prod(self.arg_params[k].shape))
                             for k in param_names)
            for tj, args in jobs:
                shard_reports.append(self._shard_audit_program(
                    tj, args, mesh=mesh, comm_spec=comm_spec,
                    overlap_plan=overlap_plan, flat_elems=flat_elems,
                    raise_on_error=not report_only))
        return {"programs": len(jobs), "wall_seconds": wall,
                "labels": [tj.label for tj, _ in jobs],
                "shard_audit": shard_reports}

    def _shard_audit_program(self, tracked, args, *, mesh, comm_spec,
                             overlap_plan, flat_elems, raise_on_error=True,
                             logger=None):
        """mxlint Pass 5 over ONE step program (analysis/sharding.py):
        trace-level MX801/MX803, and MX802 reconciliation of the warmed
        executable's optimized HLO against the SAME closed-form plan the
        program registers with the comm registry at first dispatch
        (overlap_plan.wire_plan() / allreduce_plan). ``args`` may be
        ShapeDtypeStructs (precompile) or the concrete placed step
        arguments (fit's pre-dispatch hook — the audit warms the
        TrackedJit for that signature, so the step it vouches for is the
        step that runs). Raises MXNetError on error-severity findings
        when ``raise_on_error``."""
        from . import comm as comm_mod
        from .analysis import sharding as shard_mod

        log = logger or logging
        ndev = int(mesh.shape["dp"])
        plan = None
        if ndev > 1:
            plan = (overlap_plan.wire_plan() if overlap_plan is not None
                    else comm_mod.allreduce_plan(flat_elems, ndev,
                                                 comm_spec))
        report = shard_mod.audit_step_program(
            args=args, tracked=tracked, plan=plan, compression=comm_spec,
            mesh=mesh)
        for f in report.findings:
            log.warning("shard audit [%s]: %s", tracked.label, f.format())
        if raise_on_error and report.errors:
            first = report.errors[0]
            raise MXNetError(
                f"shard audit [{tracked.label}]: the compiled step's "
                f"collective set drifted from the declared comm plan "
                f"({len(report.errors)} error(s); first: {first.rule.id} "
                f"{first.message}). Fix the drift or disable the gate "
                f"(shard_audit=False / unset MXNET_TPU_SHARD_AUDIT); see "
                f"doc/developer-guide/static_analysis.md, Pass 5")
        return report

    @staticmethod
    def _chaos_step_sites(batch_arrays, data_names, watchdog):
        """Guarded-loop fault-injection hooks (zero work unless a chaos
        injector is armed): ``step.nan`` poisons the batch so the step's
        loss/grads go non-finite; ``step.hang`` simulates a wedged step by
        stalling until the watchdog trips."""
        cz = chaos_mod.active()
        if cz is None:
            return batch_arrays
        if cz.fires("step.hang"):
            limit = time.monotonic() + (
                3.0 * watchdog.deadline if watchdog is not None else 1.0)
            while time.monotonic() < limit:
                if watchdog is not None:
                    watchdog.check()  # raises StepTimeoutError when tripped
                time.sleep(0.01)
        if cz.fires("step.nan"):
            for name in data_names:
                v = batch_arrays.get(name)
                if v is not None and jnp.issubdtype(
                        jnp.asarray(v).dtype, jnp.floating):
                    batch_arrays = dict(batch_arrays)
                    batch_arrays[name] = jnp.asarray(v) * jnp.float32("nan")
                    break
        return batch_arrays

    def _batch_to_ctx(self, arrays):
        """Place batch arrays on the ctx device. Iterators hand over
        host-committed arrays; jit follows committed inputs, so forwarding
        them unmoved would run the compiled program on the host backend
        (see _build_train_step's single-device note)."""
        dev = self.ctx[0].jax_device
        if isinstance(arrays, dict):
            return {k: _to_dev(v, dev) for k, v in arrays.items()}
        return [_to_dev(v, dev) for v in arrays]

    def _fill_missing_args(self, params, batch_arrays, symbol=None):
        """Zero-fill label args absent at inference time (forward of loss
        heads ignores labels; reference predict binds them as zeros too)."""
        symbol = symbol if symbol is not None else self.symbol
        arg_names = symbol.list_arguments()
        missing = [n for n in arg_names
                   if n not in params and n not in batch_arrays]
        if not missing:
            return batch_arrays
        known = {k: tuple(v.shape) for k, v in batch_arrays.items()}
        known.update({k: tuple(v.shape) for k, v in params.items()
                      if k in arg_names})
        arg_shapes, _, _ = symbol.infer_shape(**known)
        shape_of = dict(zip(arg_names, arg_shapes))
        out = dict(batch_arrays)
        for n in missing:
            out[n] = jnp.zeros(shape_of[n], jnp.float32)
        return out

    def _get_pred_step(self, bucket_key=None):
        """Cached jitted forward (rebuilding per call would recompile the
        whole XLA program every epoch/predict). One cache entry per bucket
        key — the jit cache is the reference's executor-per-seq-len cache."""
        if bucket_key not in self._pred_fns:
            label = (f"pred_step:{self._fingerprint_for_bucket(bucket_key)}"
                     + (f":bucket={bucket_key}" if bucket_key is not None
                        else ""))
            self._pred_fns[bucket_key] = self._build_pred_step(
                None, self._symbol_for_bucket(bucket_key), label=label)
        return self._pred_fns[bucket_key]

    def _get_eval_metric_step(self, bucket_key, eval_metric):
        """Jitted forward + on-device metric fold for full (pad-free)
        batches — the eval-side counterpart of the fused train metric."""
        key = (bucket_key, eval_metric.device_key())
        if key not in self._eval_fns:
            graph_fn = _build_graph_fn(self._symbol_for_bucket(bucket_key),
                                       is_train=False)
            update = eval_metric.device_update
            compute_dtype = self.compute_dtype

            def estep(params, aux, batch, labels, mstate):
                if compute_dtype is not None:
                    params = {k: (v.astype(compute_dtype)
                                  if jnp.issubdtype(v.dtype, jnp.floating)
                                  else v) for k, v in params.items()}
                    batch = {k: (v.astype(compute_dtype)
                                 if jnp.issubdtype(v.dtype, jnp.floating)
                                 else v) for k, v in batch.items()}
                outs, _ = graph_fn({**params, **batch}, aux,
                                   jnp.zeros((2,), jnp.uint32))
                return update(mstate, labels,
                              [o.astype(jnp.float32) for o in outs])

            self._eval_fns[key] = compile_mod.tracked_jit(
                estep, donate_argnums=(4,),
                label=(f"eval_step:{self._fingerprint_for_bucket(bucket_key)}"
                       + (f":bucket={bucket_key}" if bucket_key is not None
                          else "")))
        return self._eval_fns[key]

    def _eval(self, eval_iter, eval_metric, params, aux, data_names, label_names):
        # params may be mesh-sharded during fit; pull to the default device
        first = next(iter(params.values())) if params else None
        if first is not None and hasattr(first, "sharding") and \
                getattr(first.sharding, "num_devices", 1) > 1:
            params = {k: jnp.asarray(_host_local(v)) for k, v in params.items()}
            aux = {k: jnp.asarray(_host_local(v)) for k, v in aux.items()}
        use_device_metric = eval_metric.device_supported
        maccum = self._DeviceMetricAccum(eval_metric) if use_device_metric \
            else None
        tl = getattr(self, "_active_timeline", None)
        first_rows = {}  # bucket key -> the shape this bucket compiled for
        eval_iter.reset()
        for i, batch in enumerate(eval_iter):
            span = tl.begin_step(0, i, kind="eval_step") \
                if tl is not None else None
            try:
                bkey = getattr(batch, "bucket_key", None)
                names = getattr(batch, "data_names", data_names)
                batch_arrays = {name: arr.data
                                for name, arr in zip(names, batch.data)}
                # tail batches SHORTER than the bucket's compiled shape pad
                # up (repeat last row) instead of compiling a one-off
                # program; the extra rows join the pad slice below.
                # Iterators that pad in-place (NDArrayIter wrap-around)
                # report pad>0 and are already full-shape.
                rows = int(next(iter(batch_arrays.values())).shape[0])
                target = first_rows.setdefault(bkey, rows)
                extra = target - rows
                if extra > 0:
                    batch_arrays = _pad_rows_np(batch_arrays, extra)
                batch_arrays = self._batch_to_ctx(self._fill_missing_args(
                    params, batch_arrays,
                    symbol=self._symbol_for_bucket(bkey)))
                pad = batch.pad + max(extra, 0)
                if span is not None:
                    span.mark("dispatch")
                if use_device_metric and pad == 0:
                    # fused forward+metric, no per-batch host pull; padded
                    # tail batches (at most one per epoch) take the host
                    # path below
                    estep = self._get_eval_metric_step(bkey, eval_metric)
                    maccum.state = estep(params, aux, batch_arrays,
                                         self._batch_to_ctx(
                                             [l.data for l in batch.label]),
                                         maccum.state)
                    if span is not None:
                        span.mark("device")
                        jax.block_until_ready(maccum.state)
                    maccum.after_batch(batch.label)
                    continue
                pred = self._get_pred_step(bkey)
                outs = pred(params, aux, batch_arrays)
                if span is not None:
                    span.mark("device")
                    jax.block_until_ready(outs)
                    span.mark("host")
                nv = rows - batch.pad  # valid rows of the pre-padding batch
                outs = [NDArray(_valid_rows(o, max(rows, target), nv))
                        for o in outs]
                labels = [NDArray(l.data[:nv] if nv != l.shape[0]
                                  else l.data) for l in batch.label]
                eval_metric.update(labels, outs)
            finally:
                if span is not None:
                    span.end()
        if use_device_metric:
            maccum.finish()

    # -- inference ------------------------------------------------------------
    def predict(self, X, batch_size=128, telemetry=None, profile=None):
        """Run forward over X, concatenating outputs (reference: model.py:640).

        Returns a single numpy array for single-output nets, else a list.
        ``telemetry`` (None/True/TelemetryConfig, env gate
        ``MXNET_TPU_TELEMETRY``): record a ``predict_step`` span per batch
        on a fresh StepTimeline at ``self.telemetry``. ``profile``
        (None/True/int/ProfileConfig, env gate ``MXNET_TPU_PROFILE``):
        capture one bounded window of predict batches and attribute the
        measured device time to layers (same machinery as
        ``fit(profile=...)``; report on ``self.profile_report``)."""
        tcfg = telemetry_mod.TelemetryConfig.resolve(telemetry)
        profile_cfg = telemetry_mod.ProfileConfig.resolve(profile)
        tl = None
        if tcfg is not None and tcfg.timeline:
            tl = telemetry_mod.StepTimeline()
            self.telemetry = tl
        prof_session = None
        if profile_cfg is not None:
            prof_session = telemetry_mod.profiling.ProfileSession(
                profile_cfg,
                layers={n.name for n in self.symbol._topo()
                        if not n.is_variable},
                num_devices=1, owner="predict")
        data_iter = _init_iter(X, None, batch_size, is_train=False)
        data_names = [x[0] for x in data_iter.provide_data]
        # cross-run ledger (ISSUE 20): same window anchors as fit()
        _ledger_t0 = telemetry_mod.hub().now()
        _ledger_tic = time.time()
        if self.arg_params is None:
            raise MXNetError("model has no parameters; fit() or load first")
        params = {k: v.data for k, v in self.arg_params.items()}
        aux = {k: v.data for k, v in (self.aux_params or {}).items()}
        chunks = None
        first_rows = {}
        data_iter.reset()
        try:
          for i, batch in enumerate(data_iter):
            span = tl.begin_step(0, i, kind="predict_step") \
                if tl is not None else None
            bkey = getattr(batch, "bucket_key", None)
            pred = self._get_pred_step(bkey)
            names = getattr(batch, "data_names", data_names)
            batch_arrays = {name: arr.data for name, arr in zip(names, batch.data)}
            # pad short tail batches up to the compiled shape (see _eval)
            rows = int(next(iter(batch_arrays.values())).shape[0])
            target = first_rows.setdefault(bkey, rows)
            if target > rows:
                batch_arrays = _pad_rows_np(batch_arrays, target - rows)
            batch_arrays = self._batch_to_ctx(self._fill_missing_args(
                params, batch_arrays, symbol=self._symbol_for_bucket(bkey)))
            if span is not None:
                span.mark("dispatch")
            if prof_session is not None and prof_session.pending:
                prof_session.before_step(
                    pred, lambda: (params, aux, batch_arrays),
                    compile_mod.registry().snapshot()["compiles"])
            outs = pred(params, aux, batch_arrays)
            if prof_session is not None and prof_session.open:
                prof_session.after_step(outs)
            if span is not None:
                span.mark("device")
                jax.block_until_ready(outs)
                span.mark("host")
            nv = rows - batch.pad
            # predict materializes host outputs by contract; the pull is
            # the product, not an accident
            outs = [np.asarray(_valid_rows(o, max(rows, target), nv))  # mxlint: disable=MX309
                    for o in outs]
            if chunks is None:
                chunks = [[] for _ in outs]
            for lst, o in zip(chunks, outs):
                lst.append(o)
            if span is not None:
                span.end()
        finally:
            if tl is not None:  # exception mid-batch: drop the open span
                telemetry_mod.clear_current_span()
            if prof_session is not None:
                prof_session.close()  # short datasets close a partial window
                self.profile_report = prof_session.report
            try:
                # cross-run ledger (ISSUE 20): inference runs land in the
                # same store as fits, keyed kind="predict"
                telemetry_mod.ledger.record_run(
                    "predict",
                    fingerprint=str(self._fingerprint_for_bucket(None)),
                    world_size=1,
                    knobs={"profile": profile_cfg is not None},
                    completed=sys.exc_info()[0] is None,
                    since_ts=_ledger_t0,
                    span_name="predict_step",
                    wall_seconds=time.time() - _ledger_tic)
            except Exception as e:
                logging.warning(
                    "telemetry ledger: run record failed: %s", e)
        results = [np.concatenate(lst, axis=0) for lst in chunks]
        return results[0] if len(results) == 1 else results

    def score(self, X, *, y=None, eval_metric="accuracy", batch_size=128):
        """Evaluate a metric over a labeled dataset (capability extension;
        later-MXNet surface). X may be a DataIter with labels, or a raw
        array with labels passed as y=."""
        if hasattr(X, "provide_data"):
            if y is not None:
                raise MXNetError(
                    "score(): pass labels inside the DataIter, not as y=")
        elif y is None:
            raise MXNetError(
                "score() on a raw array needs labels: score(X, y=labels) — "
                "or pass a DataIter that provides labels")
        data_iter = _init_iter(X, y, batch_size, is_train=False)
        eval_metric = metric_mod.create(eval_metric)
        params = {k: v.data for k, v in self.arg_params.items()}
        aux = {k: v.data for k, v in (self.aux_params or {}).items()}
        data_names = [x[0] for x in data_iter.provide_data]
        label_names = [x[0] for x in data_iter.provide_label]
        self._eval(data_iter, eval_metric, params, aux, data_names, label_names)
        return eval_metric.get()[1]

    # -- persistence ----------------------------------------------------------
    def save(self, prefix, epoch=None):
        if epoch is None:
            epoch = self.num_epoch or 0
        save_checkpoint(prefix, epoch, self.symbol, self.arg_params or {},
                        self.aux_params or {})

    @staticmethod
    def load(prefix, epoch, ctx=None, **kwargs):
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return FeedForward(symbol, ctx=ctx, arg_params=arg_params,
                           aux_params=aux_params, begin_epoch=epoch, **kwargs)

    @staticmethod
    def create(symbol, X, y=None, ctx=None, num_epoch=None, optimizer="sgd",
               initializer=None, eval_data=None, eval_metric="accuracy",
               epoch_end_callback=None, batch_end_callback=None,
               kvstore="local", logger=None, batch_size=128, **kwargs):
        """Train a new model from data (reference: model.py:820-878)."""
        model = FeedForward(symbol, ctx=ctx, num_epoch=num_epoch,
                            optimizer=optimizer, initializer=initializer or
                            init_mod.Uniform(0.01), **kwargs)
        model.fit(X, y, eval_data=eval_data, eval_metric=eval_metric,
                  epoch_end_callback=epoch_end_callback,
                  batch_end_callback=batch_end_callback, kvstore=kvstore,
                  logger=logger, batch_size=batch_size)
        return model


def _valid_rows(out, fed, valid):
    """An output of a batch fed as ``fed`` rows, cut to its first ``valid``
    rows. An output with several entries a row (logits of ``(rows x
    positions, classes)``) keeps every entry of every valid row."""
    lead = out.shape[0]
    per_row = lead // fed if fed and lead % fed == 0 else 1
    return out if valid == fed else out[:valid * per_row]


def _pad_rows_np(arrays: dict, extra: int) -> dict:
    """Pad every batch array along axis 0 by repeating the last row
    ``extra`` times (host-side; eval/predict tail batches — the padded rows
    are sliced off the outputs, never observed). Delegates to
    PadPolicy.pad_arrays, the single implementation of row padding."""
    rows = next(int(v.shape[0]) for v in arrays.values()
                if getattr(v, "shape", None))
    return compile_mod.PadPolicy("bucket").pad_arrays(
        arrays, rows + extra)[0]


def _needs_commit(tree, dev):
    """First-leaf probe: does this state tree need committing to `dev`?
    State trees move as a unit (all leaves are outputs of the same step, or
    all fresh host accumulators), so one leaf answers for the tree."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return False
    first = leaves[0]
    try:
        return not (isinstance(first, jax.Array)
                    and first.devices() == {dev}
                    and getattr(first, "_committed", True))
    except Exception:  # pragma: no cover - non-Array leaves
        return True


def _needs_place(tree, mesh):
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return False
    first = leaves[0]
    return not (hasattr(first, "sharding") and
                getattr(first.sharding, "mesh", None) is mesh)


def _stored_order(symbol):
    """``{variable: axes, major to minor}``: the learnable arguments that an
    operator of ``symbol`` reads in another order than the declared one
    (``OpProp.argument_major_to_minor``). The train step keeps such a leaf
    on the device with its axes so permuted (its *stored* form; the array's
    own layout stays the default, so nothing here depends on how a compiled
    program is cached), and the graph takes it back to the declared order
    where its operator reads it (``executor._build_graph_fn``): a
    transposition that cancels against the operator's own. The choice can
    only cost time, never change a result; a variable that two nodes want
    in different orders stays as declared."""
    order, clash = {}, set()
    for node in symbol._topo():
        if node.is_variable:
            continue
        wanted = node.op.argument_major_to_minor()
        for arg, (src, _) in zip(node.op.list_arguments(), node.inputs):
            if arg in wanted and src.is_variable:
                axes = tuple(wanted[arg])
                if order.setdefault(src.name, axes) != axes:
                    clash.add(src.name)
    return {k: v for k, v in order.items()
            if k not in clash and v != tuple(range(len(v)))}


def _reorder(tree, order, back=False):
    """A tree keyed by parameter name (the parameters, or the optimizer's
    state for each) with the leaves of ``order``'s parameters taken from the
    declared axes to the stored ones, or ``back``. A state leaf follows its
    parameter where it has the parameter's rank (a moment does, a step
    count does not). Numpy or jax arrays; the very tree where ``order`` is
    empty."""
    if not order:
        return tree

    def move(x, axes):
        if back:
            axes = tuple(int(i) for i in np.argsort(axes))
        return x.transpose(axes) if np.ndim(x) == len(axes) else x

    return {k: (jax.tree_util.tree_map(lambda x: move(x, order[k]), v)
                if k in order else v) for k, v in tree.items()}
