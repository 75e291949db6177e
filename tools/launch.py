#!/usr/bin/env python
"""Multi-process job launcher (reference: dmlc-core/tracker/dmlc_local.py —
`dmlc_local.py -n <workers> -s <servers> cmd...` spawning worker and server
processes on localhost).

TPU-native version: spawns N worker processes wired together through
``jax.distributed`` (coordinator on localhost) — the single-machine,
CPU-only stand-in for a multi-host job. Every child runs with
JAX_PLATFORMS=cpu: a TPU chip belongs to one process, so N workers that
each opened the host's chips would all but one die. To train on several
chips of one host use ONE process with ``ctx=[mx.tpu(i) for i in
range(n)]`` (the dp mesh in FeedForward.fit), not this launcher. Server
processes (-s) are accepted for reference-script compatibility and launched
with DMLC_ROLE=server, where mxnet_tpu.kvstore_server retires them
immediately (no server role under sync allreduce).

Usage:
  python tools/launch.py -n 4 python my_training_script.py
Each worker gets: MXTPU_NUM_WORKERS, MXTPU_WORKER_RANK, MXTPU_COORDINATOR,
plus the reference's DMLC_* names for ported scripts.
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main():
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("-n", "--num-workers", type=int, default=1)
    ap.add_argument("-s", "--num-servers", type=int, default=0)
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")

    port = _free_port()
    # OS-assigned port for the dist_async parameter host, published to every
    # process (collision-free, unlike deriving coordinator-port+1)
    async_port = _free_port()
    while async_port == port:
        async_port = _free_port()
    coordinator = f"127.0.0.1:{port}"
    procs = []

    def env_for(role, rank):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",  # see module docstring: never the chips
            "MXTPU_NUM_WORKERS": str(args.num_workers),
            "MXTPU_COORDINATOR": coordinator,
            "MXTPU_ASYNC_PORT": str(async_port),
            # reference names, for ported scripts
            "DMLC_ROLE": role,
            "DMLC_NUM_WORKER": str(args.num_workers),
            "DMLC_NUM_SERVER": str(args.num_servers),
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
        })
        if role == "worker":
            # only workers get a worker rank: server processes retire inside
            # `import mxnet_tpu` (kvstore_server role switch) and must not
            # alias worker ranks if a script keys on this variable first
            env["MXTPU_WORKER_RANK"] = str(rank)
        else:
            env["MXTPU_SERVER_RANK"] = str(rank)
        return env

    for rank in range(args.num_workers):
        procs.append(subprocess.Popen(args.command, env=env_for("worker", rank)))
    for rank in range(args.num_servers):
        procs.append(subprocess.Popen(args.command, env=env_for("server", rank)))

    def _kill(*_a):
        for p in procs:
            p.terminate()

    signal.signal(signal.SIGINT, _kill)
    signal.signal(signal.SIGTERM, _kill)

    rc = 0
    for p in procs:
        rc = p.wait() or rc
    sys.exit(rc)


if __name__ == "__main__":
    main()
