"""Multi-process distributed tier (reference: tests/python/multi-node/,
launched there via `dmlc_local.py -n N -s S script.py`).

Spawns REAL worker processes through tools/launch.py; each joins a
jax.distributed world (CPU Gloo collectives — the single-machine stand-in
for multi-host ICI/DCN) and runs the dist_sync KVStore semantics check
ported from the reference's dist_sync_kvstore.py (closed-form BSP reduction
on small and striped-big keys).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCH = os.path.join(REPO, "tools", "launch.py")
SCRIPT = os.path.join(REPO, "examples", "distributed", "dist_sync_kvstore.py")


def _run_launch(n, timeout=240):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # 1 CPU device per process
    return subprocess.run(
        [sys.executable, LAUNCH, "-n", str(n), sys.executable, SCRIPT],
        capture_output=True, text=True, timeout=timeout, env=env)


@pytest.mark.slow
def test_dist_sync_kvstore_2proc():
    res = _run_launch(2)
    assert res.returncode == 0, res.stderr[-2000:]
    # every worker must report the closed-form BSP sum: 1+2 = 3
    assert res.stdout.count("dist_sync semantics OK (reduced value = 3)") == 2, \
        res.stdout + res.stderr[-2000:]


@pytest.mark.slow
def test_dist_sync_mlp_2proc():
    """End-to-end data-parallel training across 2 real processes
    (reference: multi-node/dist_sync_mlp.py convergence test)."""
    script = os.path.join(REPO, "examples", "distributed", "dist_sync_mlp.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    # pin the async device feed ON: this tier is what caught the round-4
    # double-_place regression (global arrays re-placed via np.asarray)
    env["MXTPU_FEED_PREFETCH"] = "2"
    res = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", sys.executable, script],
        capture_output=True, text=True, timeout=300, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.count("dist_sync_mlp accuracy") == 2, res.stdout


@pytest.mark.slow
def test_dist_sync_module_2proc():
    """Module API across 2 launched processes: kvstore-routed gradients,
    rank-0 init broadcast (per-rank seeds differ on purpose), num_workers
    rescale — both workers converge AND hold identical weights."""
    script = os.path.join(REPO, "examples", "distributed",
                          "dist_sync_module.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", sys.executable, script],
        capture_output=True, text=True, timeout=300, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.count("dist_sync_module accuracy") == 2, \
        res.stdout + res.stderr[-2000:]
    # identical replicas: both ranks print the same weight digest
    import re as _re

    digests = _re.findall(r"wsum = ([\d.]+)", res.stdout)
    assert len(digests) == 2 and digests[0] == digests[1], res.stdout


@pytest.mark.slow
def test_dist_sync_lenet_2proc():
    """Launched CONV-NET train-to-accuracy tier (reference:
    multi-node/dist_sync_lenet.py): 2 real processes, LeNet on deterministic
    4-class images, BSP-synced conv gradients, accuracy asserted on every
    worker."""
    script = os.path.join(REPO, "examples", "distributed",
                          "dist_sync_lenet.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["MXTPU_FEED_PREFETCH"] = "2"  # overlap feed stays on multi-process
    res = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", sys.executable, script],
        capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.count("dist_sync_lenet accuracy") == 2, \
        res.stdout + res.stderr[-2000:]


@pytest.mark.slow
def test_dist_sync_alexnet_2proc():
    """BASELINE.json config 5: AlexNet dist_sync across 2 launched
    processes (reference capability: dist_imagenet tiers), through the
    full example entry point — ImageRecordIter sharded by worker rank
    (num_parts/part_index), synthetic JPEG shard, BSP gradient sync."""
    script = os.path.join(REPO, "examples", "imagenet", "train_imagenet.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["MXTPU_SYNTH_IMAGES"] = "64"  # 2 batches/worker at b16: a smoke
    res = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", sys.executable, script,
         "--network", "alexnet", "--kv-store", "dist_sync", "--cpu",
         "--batch-size", "16", "--num-epochs", "1"],
        capture_output=True, text=True, timeout=900, env=env)
    out = res.stdout + res.stderr
    assert res.returncode == 0, out[-3000:]
    # both workers ran their epoch through the full example path: each
    # rank logs two Epoch[0] lines (Train-accuracy + Time cost), so a
    # single-rank run only reaches 2
    assert out.count("Epoch[0]") >= 4, out[-3000:]
    # and they really formed a 2-process world — the kvstore's fallback
    # ("continuing single-process") would otherwise pass vacuously
    assert "continuing single-process" not in out, out[-3000:]


@pytest.mark.slow
def test_launcher_accepts_server_processes():
    """-s N spawns server-role processes that retire immediately
    (no server role under sync allreduce), matching kvstore_server."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, LAUNCH, "-n", "1", "-s", "1", sys.executable, SCRIPT],
        capture_output=True, text=True, timeout=240, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "dist_sync semantics OK" in res.stdout


@pytest.mark.slow
def test_dist_async_kvstore_2proc():
    """Real update-on-arrival async PS: rank 0 pushes+pulls while rank 1 sits
    at a barrier — would deadlock under BSP (reference async semantics:
    kvstore_dist_server.h:194-202)."""
    script = os.path.join(REPO, "examples", "distributed",
                          "dist_async_kvstore.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", sys.executable, script],
        capture_output=True, text=True, timeout=240, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.count("dist_async semantics OK (value = 5)") == 2, \
        res.stdout + res.stderr[-2000:]


@pytest.mark.slow
def test_dist_async_staleness_4proc():
    """4 workers at skewed speeds (rank*50ms per batch): every worker
    completes unblocked, the server's update_count equals the total pushed
    batches, and training converges despite stale gradients."""
    script = os.path.join(REPO, "examples", "distributed",
                          "dist_async_staleness.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, LAUNCH, "-n", "4", sys.executable, script],
        capture_output=True, text=True, timeout=300, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "dist_async_staleness OK" in res.stdout, \
        res.stdout + res.stderr[-2000:]
    assert res.stdout.count("completed 12 batches") == 4, res.stdout


@pytest.mark.slow
def test_dist_async_lenet_2proc():
    """Async-PS CONV-NET tier (reference: multi-node/dist_async_lenet.py):
    conv gradients to the update-on-arrival parameter host, accuracy
    asserted on both workers."""
    script = os.path.join(REPO, "examples", "distributed",
                          "dist_async_lenet.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", sys.executable, script],
        capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.count("dist_async_lenet accuracy") == 2, \
        res.stdout + res.stderr[-2000:]


@pytest.mark.slow
def test_dist_async_mlp_2proc():
    """End-to-end async-PS training across 2 real processes: optimizer on
    the parameter host, per-batch push/pull, no collectives (reference:
    multi-node/dist_async_mlp.py convergence test)."""
    script = os.path.join(REPO, "examples", "distributed", "dist_async_mlp.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", sys.executable, script],
        capture_output=True, text=True, timeout=300, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.count("dist_async_mlp accuracy") == 2, \
        res.stdout + res.stderr[-2000:]


def test_dist_async_wire_throughput_single_process(monkeypatch):
    """Transport characterization: the raw-buffer frame path must move
    tensor payloads as their own bytes through the loopback parameter
    host (the old pickled-float wire put the floats through the pickler).
    Held as a count: what crosses the socket in a push_pull round is the
    16 MB model twice (push + reply) plus headers of a few hundred bytes,
    and no frame's pickled header grows with the payload."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import kvstore_async
    from mxnet_tpu.kvstore_async import AsyncKVStore

    kv = AsyncKVStore()  # standalone: loopback host on an os-assigned port
    rng = np.random.RandomState(0)
    model = {f"w{i}": rng.randn(1024, 1024).astype(np.float32)
             for i in range(4)}  # 16 MB
    for k, v in model.items():
        kv.init(k, mx.nd.array(v))
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.0))

    # client and in-process server frame through the one _encode_msg
    frames = []
    encode = kvstore_async._encode_msg

    def counting_encode(obj):
        pieces = encode(obj)
        frames.append((len(pieces[1]),
                       sum(memoryview(p).nbytes for p in pieces)))
        return pieces

    monkeypatch.setattr(kvstore_async, "_encode_msg", counting_encode)
    nbytes = sum(v.nbytes for v in model.values())
    rounds = 6
    for _ in range(rounds):
        out = kv.push_pull(model)
    assert set(out) == set(model)
    for k, v in model.items():  # lr 0: the reply is the model, bit for bit
        np.testing.assert_array_equal(np.asarray(out[k]), v)
    # each round moves the payload twice (push + reply), as raw buffers
    assert len(frames) == 2 * rounds, len(frames)
    wire = sum(total for _, total in frames)
    assert 2 * rounds * nbytes <= wire <= 2 * rounds * (nbytes + 4096), \
        (wire, 2 * rounds * nbytes)
    assert max(header for header, _ in frames) < 4096, frames
