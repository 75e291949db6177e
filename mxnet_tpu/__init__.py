"""mxnet_tpu: a TPU-native deep-learning framework with the capability surface
of early MXNet (the v0.5-era reference), built on JAX/XLA/pjit/Pallas.

Layering (cf. SURVEY.md §1):
  context/base/engine      - device model, errors, host async engine
  ndarray/random           - imperative tensors over jax.Array
  ops/                     - operator library (registry + pure-fn kernels)
  symbol/executor          - symbolic graphs tracing to jitted XLA programs
  io/                      - data iterators (RecordIO/MNIST/NDArray, prefetch)
  kvstore                  - data-parallel parameter sync over mesh collectives
  model/optimizer/metric/  - FeedForward trainer stack
  initializer/callback
  parallel/                - meshes, shard specs, collectives, ring attention
  models/                  - the model zoo (MLP..ResNet-50, LSTM, transformer)
  compat                   - JAX version shims (the only module allowed to
                             probe fragile API locations; mxlint MX101)
  analysis/                - mxlint: source lint, Symbol.verify graph pass,
                             jaxpr audit (doc/developer-guide/static_analysis.md)
  resilience/              - fault tolerance: chaos injection, retrying
                             kvstore transport + circuit breaker, step
                             guards/watchdog, preemption-safe checkpoints
                             (doc/developer-guide/resilience.md)
  telemetry/               - observability: metrics hub (counters/gauges/
                             histograms + event ring), per-step timeline
                             tracing, MFU/goodput accounting, Prometheus/
                             JSONL/Chrome-trace exporters
                             (doc/developer-guide/telemetry.md)
"""

# Join the jax.distributed world BEFORE anything touches a backend: under
# tools/launch.py each worker must initialize from the coordinator env vars
# prior to the first jax call, or XLA pins a single-process backend and
# dist_sync silently degrades to N independent runs (reference analog: the
# DMLC_* wiring happens at import via kvstore_server's role switch).
def _join_launcher_world():
    import os

    coord = os.environ.get("MXTPU_COORDINATOR")
    nproc = int(os.environ.get("MXTPU_NUM_WORKERS", "1"))
    rank = os.environ.get("MXTPU_WORKER_RANK")
    if not coord or nproc <= 1 or rank is None:
        return
    import jax

    from .compat import distributed_initialized

    if distributed_initialized():
        return
    jax.distributed.initialize(coord, num_processes=nproc,
                               process_id=int(rank))


_join_launcher_world()

from . import base, compat, context, engine
from .base import MXNetError
from .context import Context, cpu, cpu_pinned, current_context, gpu, num_devices, tpu
from . import ndarray
from . import ndarray as nd
from . import random
from .ndarray import NDArray

from . import ops
from . import symbol
from . import symbol as sym
from .symbol import Symbol, Variable, Group
from .executor import Executor

from . import initializer as init
from . import initializer
from . import io
from . import kvstore as kv
from . import kvstore
# import-time role switch: a process with DMLC_ROLE=server/scheduler retires
# here (reference: kvstore_server.py:48-58 runs the server loop inside
# `import mxnet`; on TPU there is no server loop to run)
from . import kvstore_server
from . import metric
from . import optimizer
from . import callback
from . import lr_scheduler
from . import visualization as viz
from . import visualization
from . import monitor
from .monitor import Monitor
from . import operator
from . import model
from .model import FeedForward
from . import module as mod
from .module import Module
from . import bucketing
from .bucketing import BucketingFeedForward, BucketSentenceIter
from . import recordio
from . import parallel
from . import comm
from . import models
from . import utils

# Persistent XLA compilation cache (doc/developer-guide/compile_cache.md):
# JAX_COMPILATION_CACHE_DIR when the environment sets it, else
# <checkout>/.jax_cache — must be resolved before the first compile
# dispatches.
utils.compile.configure_persistent_cache()
from . import predictor as _predictor_mod
from .predictor import Predictor
from . import analysis
from . import resilience
from . import telemetry

# Background /metrics endpoint (Prometheus text): opt-in via
# MXNET_TPU_METRICS_PORT so long-running jobs are scrapable with zero code.
telemetry.maybe_serve_http_from_env()

__version__ = "0.1.0"
