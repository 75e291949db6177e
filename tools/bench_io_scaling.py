"""Native input-pipeline decode scaling characterization (CPU-only).

Substantiates the claim "the native ImageRecordIter pipeline scales with
decode worker threads" with measurements rather than
assertion. Reference anchor: the original's OpenMP decode
(src/io/iter_image_recordio.cc:187) and its 3,000 img/s HDD figure
(example/imagenet/README.md:5).

This rig has ONE cpu core (nproc=1), so an 8-core speedup curve cannot be
measured directly. What CAN be measured honestly:

1. per-core full-pipeline throughput (1 thread) — the scaling unit;
2. the per-stage split: MXTPU_NATIVE_SKIP_DECODE=1 keeps everything but the
   JPEG decode (so decode share is t_full - t_nodecode), and
   MXTPU_NATIVE_SKIP_WORK=1 delivers zeroed batches, measuring ONLY the
   serial path — per-batch ticketing plus the ordered delivery memcpy in
   Next(). Everything else (read, CRC, decode, resize, crop, assembly) runs
   inside ProduceBatch on the worker threads, i.e. is parallel by
   construction;
3. aggregate throughput at 1/2/4/8 threads ON THE SINGLE CORE — if the
   worker pool had lock contention or convoying, adding threads on one core
   would *reduce* throughput; flat means the coordination cost is nil;
4. an Amdahl projection for an 8-core host: serial term from (2)'s
   skip-work floor, parallel term = the rest.

Writes io_scaling JSON lines and a summary.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import ensure_recordio  # noqa: E402
from mxnet_tpu import native  # noqa: E402


def run_epochs(path, offsets, nthreads, batch=64, epochs=2, skip_decode=False,
               skip_work=False):
    """img/s over the steady epoch (first epoch warms page cache/threads)."""
    for var, on in (("MXTPU_NATIVE_SKIP_DECODE", skip_decode),
                    ("MXTPU_NATIVE_SKIP_WORK", skip_work)):
        if on:
            os.environ[var] = "1"
        else:
            os.environ.pop(var, None)
    pipe = native.NativePipeline(
        path, offsets, batch, (3, 224, 224), rand_crop=True, rand_mirror=True,
        resize=256, shuffle=True, seed=3, num_threads=nthreads, prefetch=8,
        nhwc=True, out_u8=True)
    n = 0
    for _ in range(max(1, epochs - 1)):  # warm epochs
        while True:
            try:
                pipe.next()
            except StopIteration:
                break
            n += 1
        pipe.reset()
    t0 = time.perf_counter()
    m = 0
    while True:
        try:
            _, _, pad = pipe.next()
        except StopIteration:
            break
        m += 1
    dt = time.perf_counter() - t0
    del pipe
    return m * batch / dt


def main():
    path = ensure_recordio("/tmp/mxtpu_bench_imagenet.rec", n=1024)
    offsets = native.scan_offsets(path)
    assert offsets, "native scanner unavailable"

    results = {"host_cores": os.cpu_count(), "records": []}

    for nt in (1, 2, 4, 8):
        ips = run_epochs(path, offsets, nt)
        results["records"].append(
            {"threads": nt, "decode": True, "img_per_sec": round(ips, 1)})
        print(json.dumps(results["records"][-1]))

    nodecode = run_epochs(path, offsets, 1, skip_decode=True)
    results["records"].append(
        {"threads": 1, "stage": "no_decode", "img_per_sec": round(nodecode, 1)})
    print(json.dumps(results["records"][-1]))

    serial_only = run_epochs(path, offsets, 1, skip_work=True)
    results["records"].append(
        {"threads": 1, "stage": "serial_path_only",
         "img_per_sec": round(serial_only, 1)})
    print(json.dumps(results["records"][-1]))

    base = results["records"][0]["img_per_sec"]
    multi = [r["img_per_sec"] for r in results["records"][:4]]
    t_full = 1.0 / base                  # sec per image, 1 thread
    t_serial = 1.0 / serial_only         # delivery/ticketing sec per image
    decode_share = 1.0 - base / nodecode if nodecode > base else 0.0
    p = 1.0 - t_serial / t_full          # in-worker (parallel) fraction
    amdahl8 = 1.0 / ((1 - p) + p / 8)
    results.update({
        "single_core_img_per_sec": base,
        "decode_share_of_worker_cost": round(decode_share, 4),
        "serial_path_img_per_sec": round(serial_only, 1),
        "parallel_fraction": round(p, 4),
        "multi_thread_on_one_core_flat": bool(min(multi) > 0.85 * base),
        "amdahl_projected_speedup_8_cores": round(amdahl8, 2),
        "amdahl_projected_img_per_sec_8_cores": round(base * amdahl8, 1),
        "note": "1-core rig: threads>1 cannot exceed 1x; flatness across "
                "1..8 threads shows zero coordination overhead; serial term "
                "= ordered-delivery memcpy + ticketing only (everything "
                "else runs inside worker threads by construction).",
    })
    print(json.dumps({k: v for k, v in results.items() if k != "records"}))
    with open("IO_SCALING_r03.json", "w") as f:
        json.dump(results, f, indent=1)
    print("wrote IO_SCALING_r03.json")


if __name__ == "__main__":
    main()
