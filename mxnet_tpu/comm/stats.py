"""Wire-byte accounting: comm plans, the process registry, HLO extraction.

Three complementary sources of truth:

  1. **Plan arithmetic** (:func:`allreduce_plan`) — the compressed
     allreduce's shapes are trace-time constants, so its payload and wire
     bytes are exact closed-form numbers, available before anything
     compiles. Wire bytes use the standard ring-algorithm factors:

         all-reduce          2·(n-1)/n · payload     (reduce-scatter +
                                                      all-gather phases)
         all-gather          (n-1)/n · output bytes
         reduce-scatter      (n-1)/n · input bytes
         all-to-all          (n-1)/n · payload
         collective-permute  1 · payload

  2. **The CommRegistry** — per-program plans plus per-step dispatch
     counters, so ``comm_stats()`` answers "how many bytes crossed the
     wire this epoch, at what ratio vs fp32" for the whole process (the
     compile-registry pattern from utils/compile applied to comm).

  3. **HLO extraction** (:func:`hlo_collective_table`) — parse the
     compiled program's collective instructions (opcode, operand shapes,
     replica groups) into the same row shape, applying the same wire
     factors. This is the cross-check: the plan says what we built, the
     HLO says what XLA actually lowered (extends the test_comm_plan.py
     machinery; tests/test_comm.py asserts the two agree).
"""

from __future__ import annotations

import re

from ..analysis.lockwatch import named_lock
from .compression import CompressionSpec, payload_nbytes, quantization_unit

__all__ = ["allreduce_plan", "overlap_plan", "fp32_allreduce_wire_bytes",
           "CommRegistry", "registry", "comm_stats", "reset_comm_stats",
           "hlo_collective_table", "hlo_collective_rows",
           "hlo_collective_wire_bytes",
           "hlo_elementwise_table", "hlo_quantize_pass_count"]


# -- plan arithmetic -----------------------------------------------------------

def fp32_allreduce_wire_bytes(num_elements: int, axis_size: int) -> float:
    """Ring all-reduce wire cost of the uncompressed baseline."""
    n = int(axis_size)
    return 2.0 * (n - 1) / n * 4.0 * int(num_elements)


def allreduce_plan(num_elements: int, axis_size: int,
                   compression=None) -> dict:
    """Exact per-step comm plan for one fused gradient allreduce.

    Returns ``{"collectives": [rows], "payload_bytes", "wire_bytes",
    "fp32_wire_bytes", "ratio", ...}`` where each row is
    ``{"op", "count", "payload_bytes", "wire_bytes"}`` and ``ratio`` is
    fp32-wire / this-wire (>1 = the compression saves bytes).
    """
    n = int(axis_size)
    L = int(num_elements)
    spec = CompressionSpec.resolve(compression)
    fp32_wire = fp32_allreduce_wire_bytes(L, n)
    if spec is None:
        rows = [{"op": "all-reduce", "count": 1, "payload_bytes": 4 * L,
                 "wire_bytes": fp32_wire}]
        mode = "none"
    else:
        unit = quantization_unit(spec) * n
        Lp = -(-L // unit) * unit
        per = Lp // n
        p1 = payload_nbytes(spec, Lp)             # stage-1 rows, all devices
        gspec = CompressionSpec("bf16") if spec.mode == "twobit" else spec
        p2 = payload_nbytes(gspec, per)           # stage-2 reduced shard
        rows = [
            {"op": "all-to-all", "count": 1, "payload_bytes": p1,
             "wire_bytes": (n - 1) / n * p1},
            {"op": "all-gather", "count": 1, "payload_bytes": n * p2,
             "wire_bytes": (n - 1) * p2},
        ]
        mode = spec.mode
    payload = sum(r["payload_bytes"] for r in rows)
    wire = sum(r["wire_bytes"] for r in rows)
    return {
        "mode": mode, "num_elements": L, "axis_size": n,
        "collectives": rows, "payload_bytes": payload, "wire_bytes": wire,
        "fp32_wire_bytes": fp32_wire,
        "ratio": fp32_wire / wire if wire else float("inf"),
    }


def overlap_plan(bucket_elems, axis_size, compression=None) -> dict:
    """Exact per-step comm plan for an overlapped per-bucket schedule.

    ``bucket_elems``: ``[(bucket_name, num_elements), ...]`` in schedule
    order (``OverlapPlan.bucket_elems()``). Each bucket gets its own
    closed-form :func:`allreduce_plan`; the merged totals are computed
    from the SUMMED integer payload bytes, and because payload bytes are
    linear in the padded length, they equal — exactly, not approximately —
    the fused single-bucket plan over the same padded total
    (``fused_wire_bytes`` / ``matches_fused``). The overlapped schedule
    therefore moves the same bytes as the fused one plus only the
    per-bucket padding slack, which ``padded_elements - num_elements``
    prices explicitly.
    """
    n = int(axis_size)
    spec = CompressionSpec.resolve(compression)
    buckets = []
    for name, num in bucket_elems:
        p = allreduce_plan(num, n, spec)
        buckets.append({"bucket": name, **p})
    # merge rows by opcode, summing the integer payloads first and applying
    # the wire factor to the SUM — float-exact against the fused plan
    merged: dict[str, dict] = {}
    for b in buckets:
        for r in b["collectives"]:
            row = merged.setdefault(r["op"], {"op": r["op"], "count": 0,
                                              "payload_bytes": 0})
            row["count"] += r["count"]
            row["payload_bytes"] += r["payload_bytes"]
    raw_total = sum(int(num) for _, num in bucket_elems)
    if spec is None:
        padded_total = raw_total
        for row in merged.values():
            row["wire_bytes"] = 2.0 * (n - 1) / n * row["payload_bytes"]
    else:
        unit = quantization_unit(spec) * n
        padded_total = sum(-(-int(num) // unit) * unit
                           for _, num in bucket_elems)
        # both compressed rows carry wire = (n-1)/n x payload (the
        # all-gather payload is already the full gathered buffer), so the
        # factor applies uniformly to the integer payload sums
        for row in merged.values():
            row["wire_bytes"] = (n - 1) / n * row["payload_bytes"]
    rows = sorted(merged.values(), key=lambda r: r["op"])
    payload = sum(r["payload_bytes"] for r in rows)
    wire = sum(r["wire_bytes"] for r in rows)
    fused = allreduce_plan(padded_total, n, spec)
    fp32_wire = fp32_allreduce_wire_bytes(raw_total, n)
    return {
        "mode": "none" if spec is None else spec.mode,
        "num_elements": raw_total, "padded_elements": padded_total,
        "axis_size": n, "num_buckets": len(buckets), "buckets": buckets,
        "collectives": rows, "payload_bytes": payload, "wire_bytes": wire,
        "fp32_wire_bytes": fp32_wire,
        "ratio": fp32_wire / wire if wire else float("inf"),
        "fused_wire_bytes": fused["wire_bytes"],
        "matches_fused": wire == fused["wire_bytes"],
    }


# -- process-wide registry -----------------------------------------------------

class CommRegistry:
    """Per-program comm plans + per-step wire counters (thread-safe)."""

    def __init__(self):
        # constructed unconditionally BEFORE reset(): the old
        # `getattr(self, "_lock", threading.Lock())` fallback locked a
        # fresh private lock when _lock was missing, guarding nothing
        # (the MX705 bug class — this line is the rule's citation)
        self._lock = named_lock("comm.CommRegistry")
        self.reset()

    def reset(self):
        with self._lock:
            self._plans = {}
            self._steps = {}
            self._extra_bytes = {"sent": 0.0, "received": 0.0}

    def register_plan(self, label: str, plan: dict):
        with self._lock:
            self._plans[label] = dict(plan)
            self._steps.setdefault(label, 0)

    def record_step(self, label: str, count: int = 1):
        """One (or ``count``) dispatches of ``label``'s per-step plan."""
        with self._lock:
            self._steps[label] = self._steps.get(label, 0) + int(count)

    def record_host_bytes(self, sent=0, received=0):
        """Fold host-transport traffic (kvstore sockets) into the totals."""
        with self._lock:
            self._extra_bytes["sent"] += int(sent)
            self._extra_bytes["received"] += int(received)

    def snapshot(self) -> dict:
        """Cheap totals copy for before/after diffing (epoch logs)."""
        with self._lock:
            steps = sum(self._steps.values())
            wire = sum(self._steps.get(k, 0) * p["wire_bytes"]
                       for k, p in self._plans.items())
            fp32 = sum(self._steps.get(k, 0) * p["fp32_wire_bytes"]
                       for k, p in self._plans.items())
            host = self._extra_bytes["sent"] + self._extra_bytes["received"]
            return {"steps": steps, "wire_bytes": wire + host,
                    "fp32_wire_bytes": fp32, "host_bytes": host}

    def stats(self) -> dict:
        with self._lock:
            per = {}
            for label, plan in self._plans.items():
                steps = self._steps.get(label, 0)
                per[label] = {**plan, "steps": steps,
                              "total_wire_bytes": steps * plan["wire_bytes"]}
            steps = sum(self._steps.values())
            wire = sum(c["total_wire_bytes"] for c in per.values())
            fp32 = sum(self._steps.get(k, 0) * p["fp32_wire_bytes"]
                       for k, p in self._plans.items())
            host = dict(self._extra_bytes)
            total = wire + host["sent"] + host["received"]
            return {
                "steps": steps,
                "wire_bytes": total,
                "collective_wire_bytes": wire,
                "fp32_wire_bytes": fp32,
                "ratio": (fp32 / wire) if wire else None,
                "host_bytes": host,
                "per_program": per,
            }


_REGISTRY = None


def registry() -> CommRegistry:
    global _REGISTRY
    if _REGISTRY is None:
        _REGISTRY = CommRegistry()
    return _REGISTRY


def comm_stats() -> dict:
    """Process-wide wire accounting (see CommRegistry)."""
    return registry().stats()


def reset_comm_stats():
    registry().reset()


# -- HLO extraction ------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
                "f32": 4, "s32": 4, "u32": 4,
                "f64": 8, "s64": 8, "u64": 8}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# "%name = <result-shape> <opcode>(..." — result shape may be a tuple;
# async variants appear as <opcode>-start (skip -done: same traffic)
_INSTR_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVES) + r")(-start)?\(")
_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|"
                       r"u64)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_FULL_GROUPS_RE = re.compile(r"replica_groups=(\{\{.*?\}\})")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _typed_shapes(shape_str: str) -> list:
    """Every ``dtype[dims]`` token in a result shape as
    ``{"dtype", "elements", "bytes"}`` — one entry per tuple member."""
    parts = []
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        parts.append({"dtype": dtype, "elements": n,
                      "bytes": n * _DTYPE_BYTES[dtype]})
    return parts


def _replica_groups(line: str, default: int):
    """``(num_groups, group_size)`` of an instruction's replica groups;
    ``num_groups`` is ``None`` when the HLO names no groups (then
    ``group_size`` is the caller's default)."""
    m = _FULL_GROUPS_RE.search(line)
    if m:
        text = m.group(1)
        first = _GROUPS_RE.search(line)
        ids = [g for g in first.group(1).split(",") if g.strip()] \
            if first else []
        size = max(len(ids), 1)
        return max(text.count("{") - 1, 1), size
    m = _IOTA_GROUPS_RE.search(line)
    if m:  # iota form [num_groups, group_size]<=[...]
        return max(int(m.group(1)), 1), max(int(m.group(2)), 1)
    return None, default


def _group_size(line: str, default: int) -> int:
    return _replica_groups(line, default)[1]


def _wire_bytes(op: str, result_bytes: int, n: int) -> float:
    if n <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (n - 1) / n * result_bytes
    if op == "all-gather":          # result is the full gathered buffer
        return (n - 1) / n * result_bytes
    if op == "reduce-scatter":      # result is one shard; input was n shards
        return float((n - 1) * result_bytes)
    if op == "all-to-all":
        return (n - 1) / n * result_bytes
    return float(result_bytes)      # collective-permute


def hlo_collective_rows(hlo_text: str, default_group_size: int = 1) -> list:
    """Per-INSTANCE collective rows from compiled HLO — the detailed form
    the MX802 reconciliation (analysis/sharding.py) audits.

    Each row: ``{"op", "async", "payload_bytes", "wire_bytes",
    "group_size", "replica_groups", "parts"}`` where ``replica_groups``
    is ``(num_groups, group_size)`` (``num_groups`` None when the HLO
    names no groups) and ``parts`` is the per-dtype payload breakdown
    ``[{"dtype", "elements", "bytes"}, ...]`` — one part per tuple member
    for combined collectives, exactly the logical payload member for
    async ``-start`` halves (``-done`` halves are skipped).
    """
    rows = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        is_async = bool(m.group(3))
        if is_async and shape_str.startswith("("):
            # async -start: result is a tuple aliasing operand and result
            # buffers; the op's logical result is the LARGEST member
            # (== result for all-gather, == either for all-reduce) except
            # for reduce-scatter, whose result is the small shard
            members = _typed_shapes(shape_str)
            if members:
                pick = min if op == "reduce-scatter" else max
                parts = [pick(members, key=lambda p: p["bytes"])]
                payload = parts[0]["bytes"]
            else:
                parts = []
                payload = _shape_bytes(shape_str) // 2
        else:
            parts = _typed_shapes(shape_str)
            payload = _shape_bytes(shape_str)
        num_groups, n = _replica_groups(line, default_group_size)
        rows.append({
            "op": op, "async": is_async, "payload_bytes": payload,
            "wire_bytes": _wire_bytes(op, payload, n),
            "group_size": n, "replica_groups": (num_groups, n),
            "parts": parts,
        })
    return rows


def hlo_collective_table(hlo_text: str, default_group_size: int = 1) -> list:
    """Parse compiled HLO into per-opcode collective byte rows.

    Each row: ``{"op", "count", "payload_bytes", "wire_bytes"}`` — payload
    is the summed result-shape bytes of every instance; wire applies the
    ring factors above with the instruction's replica-group size
    (``default_group_size`` when the HLO names no groups). ``-start``
    async variants count once; ``-done`` halves are skipped. Also carries
    the per-collective detail ISSUE 16 added: ``"elements"`` (summed
    payload element count), ``"dtypes"`` (sorted payload dtypes), and
    ``"replica_groups"`` (sorted distinct ``(num_groups, group_size)``
    shapes) — aggregated from :func:`hlo_collective_rows`.
    """
    by_op: dict[str, dict] = {}
    for r in hlo_collective_rows(hlo_text, default_group_size):
        row = by_op.setdefault(r["op"], {
            "op": r["op"], "count": 0, "payload_bytes": 0,
            "wire_bytes": 0.0, "elements": 0, "dtypes": set(),
            "replica_groups": set()})
        row["count"] += 1
        row["payload_bytes"] += r["payload_bytes"]
        row["wire_bytes"] += r["wire_bytes"]
        row["elements"] += sum(p["elements"] for p in r["parts"])
        row["dtypes"].update(p["dtype"] for p in r["parts"])
        row["replica_groups"].add(r["replica_groups"])
    for row in by_op.values():
        row["dtypes"] = sorted(row["dtypes"])
        row["replica_groups"] = sorted(
            row["replica_groups"],
            key=lambda g: (g[0] is None, g))
    return sorted(by_op.values(), key=lambda r: -r["wire_bytes"])


def hlo_collective_wire_bytes(hlo_text: str,
                              default_group_size: int = 1) -> float:
    """Total wire bytes of every collective in a compiled HLO module."""
    return sum(r["wire_bytes"] for r in
               hlo_collective_table(hlo_text, default_group_size))


# -- elementwise-pass extraction ----------------------------------------------
# The encode/decode cost the fused comm kernels (ops/pallas/comm_kernels)
# exist to remove shows up in HLO as full-slab elementwise instructions:
# each quantize stage is a chain of round/clamp/divide/... ops whose
# result covers the whole gradient slab. Counting instructions at or
# above a slab-sized element threshold measures exactly that — the
# kernel path's quantize math lives inside per-BLOCK kernel bodies, so
# its instructions stay under the threshold and the full-slab count
# drops (asserted by tests/test_pallas_kernels.py).

_GENERIC_INSTR_RE = re.compile(
    r"=\s*((?:pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)"
    r"\[[\d,]*\])\S*\s+([a-z][a-z0-9-]*)\(")

# the opcodes a quantize/dequantize stage is made of
_QUANTIZE_OPS = frozenset({
    "round-nearest-even", "round-nearest-afz", "clamp", "divide",
    "multiply", "abs", "maximum", "minimum",
})


def _shape_elems(shape_str: str) -> int:
    m = _SHAPE_RE.match(shape_str)
    if not m:
        return 0
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    return n


def hlo_elementwise_table(hlo_text: str, min_elements: int = 0,
                          ops=None) -> list:
    """Per-opcode counts of (large) elementwise-shaped HLO instructions.

    Each row: ``{"op", "count", "elements"}`` for instructions whose
    result holds at least ``min_elements`` elements; ``ops`` restricts to
    an opcode set (default: every matched opcode). Fusion-computation
    bodies count too — a pass is a pass wherever XLA parked it."""
    by_op: dict[str, dict] = {}
    for line in hlo_text.splitlines():
        m = _GENERIC_INSTR_RE.search(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        if ops is not None and op not in ops:
            continue
        elems = _shape_elems(shape_str)
        if elems < min_elements:
            continue
        row = by_op.setdefault(op, {"op": op, "count": 0, "elements": 0})
        row["count"] += 1
        row["elements"] += elems
    return sorted(by_op.values(), key=lambda r: (-r["count"], r["op"]))


def hlo_quantize_pass_count(hlo_text: str, min_elements: int) -> int:
    """How many full-slab quantize-shaped passes a compiled module runs:
    the encode/decode HLO op-count metric the fused comm kernels are
    measured by (lower is better; the wire bits are identical)."""
    return sum(r["count"] for r in
               hlo_elementwise_table(hlo_text, min_elements,
                                     ops=_QUANTIZE_OPS))
