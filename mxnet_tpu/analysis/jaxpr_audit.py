"""mxlint Pass 3: audit the traced jaxpr of a bound executor.

Where Pass 1 sees source and Pass 2 sees the symbolic DAG, this pass sees
what will actually run: the jaxpr XLA compiles. It reports

  MX501  host callbacks / debug prints inside the compiled program (each
         one stalls the TPU pipeline on a host round-trip),
  MX502  unexpected dtype promotions — e.g. f32 tensors materializing in
         a program the caller intends to run in bf16,

and produces per-primitive FLOP/byte totals. It works pre-XLA, so its
bytes bound the *unfused* traffic; a per-instruction table over the
optimized HLO bounds it from the other side.

jax is imported lazily (function scope) so importing the analysis package
never pulls in the tracing machinery until an audit actually runs.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

from .rules import Finding, get_rule

__all__ = ["audit_jaxpr", "audit_executor", "AuditReport", "cost_rows"]

# primitives that round-trip to the host from inside the compiled program
HOST_TRANSFER_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "host_callback_call", "outside_call", "infeed", "outfeed",
}

# primitives with inner jaxprs to recurse into, by param key
_INNER_JAXPR_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr",
                      "body_jaxpr")

# collective primitives: traced-level comm ops (explicit shard_map
# collectives; the SPMD partitioner's implicit psums only exist post-HLO —
# comm.stats.hlo_collective_table covers that side)
COLLECTIVE_PRIMS = {"psum", "pmean", "pmax", "pmin", "all_gather",
                    "all_to_all", "ppermute", "pshuffle", "psum_scatter",
                    "reduce_scatter"}


@dataclass
class AuditReport:
    findings: list = field(default_factory=list)
    totals: dict = field(default_factory=dict)    # {'flops': .., 'bytes': ..}
    rows: list = field(default_factory=list)      # per-primitive table
    comm_rows: list = field(default_factory=list)  # per-collective table

    @property
    def errors(self):
        return [f for f in self.findings if f.is_error]


def _aval_bytes(aval):
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0
    return int(size) * dtype.itemsize


def _iter_eqns(jaxpr, skip_inner=None):
    """Yield every eqn in the jaxpr, recursing through nested jaxprs.

    ``skip_inner(eqn) -> bool`` suppresses recursion into an eqn's inner
    jaxprs — how registry-attributed ``pallas_call`` regions avoid double
    counting (the kernel body describes ONE grid cell; the registry's
    model prices the whole call)."""
    for eqn in jaxpr.eqns:
        # evaluate BEFORE yielding: the consumer reads the attribution
        # side effect for this eqn as soon as it receives it
        skip = skip_inner is not None and skip_inner(eqn)
        yield eqn
        if skip:
            continue
        for key in _INNER_JAXPR_PARAMS:
            sub = eqn.params.get(key)
            if sub is None:
                continue
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield from _iter_eqns(inner, skip_inner)
        for branch in eqn.params.get("branches", ()):
            inner = getattr(branch, "jaxpr", branch)
            if hasattr(inner, "eqns"):
                yield from _iter_eqns(inner, skip_inner)


def _eqn_flops(eqn):
    """FLOP estimate for one eqn (2*MACs for contractions, out-size for
    elementwise; 0 for layout/metadata ops)."""
    name = eqn.primitive.name
    outs = [v.aval for v in eqn.outvars]
    out_size = sum(getattr(a, "size", 0) for a in outs)
    if name == "dot_general":
        lhs, rhs = (v.aval for v in eqn.invars[:2])
        (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
        contract = math.prod(lhs.shape[d] for d in lc) or 1
        batch = math.prod(lhs.shape[d] for d in lb) or 1
        lhs_free = lhs.size // max(contract * batch, 1)
        rhs_free = rhs.size // max(contract * batch, 1)
        return 2 * batch * lhs_free * rhs_free * contract
    if name == "conv_general_dilated":
        rhs = eqn.invars[1].aval
        dn = eqn.params["dimension_numbers"]
        out_feature_dim = dn.rhs_spec[0]
        groups = eqn.params.get("feature_group_count", 1)
        per_out = 2 * rhs.size // max(rhs.shape[out_feature_dim], 1) // groups
        return out_size * per_out
    if name in ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                "argmax", "argmin", "cumsum", "cumlogsumexp"):
        return sum(getattr(v.aval, "size", 0) for v in eqn.invars)
    if name in ("broadcast_in_dim", "reshape", "transpose", "squeeze",
                "convert_element_type", "slice", "dynamic_slice", "concatenate",
                "gather", "scatter", "pad", "rev", "iota", "copy"):
        return 0
    return out_size


def _byte_cost(eqn):
    return (sum(_aval_bytes(v.aval) for v in eqn.invars if hasattr(v, "aval"))
            + sum(_aval_bytes(v.aval) for v in eqn.outvars))


def _is_float(dtype):
    import numpy as np

    return np.issubdtype(dtype, np.floating)


def audit_jaxpr(closed_jaxpr, intended_dtype=None,
                attribute_kernels=True) -> AuditReport:
    """Audit a ClosedJaxpr: host transfers, dtype promotions, cost table.

    ``intended_dtype``: the dtype the program is supposed to compute in
    (e.g. jnp.bfloat16). Any eqn producing a *wider* float output from
    inputs of the intended dtype is flagged MX502 — except dot_general /
    conv, where a wider accumulator is the correct MXU usage.

    ``attribute_kernels``: price registered Pallas kernels through the
    kernel registry (ops/pallas/registry.py) — a ``pallas_call`` whose
    ``name=`` has a registered FLOP/byte model lands as its own
    ``pallas::<name>`` row and its inner jaxpr is NOT recursed into
    (which would count one grid cell and under-report by the grid size —
    the pre-registry behavior that made flash attention invisible to the
    MFU accountant). Unregistered pallas calls keep the legacy path.
    """
    import numpy as np

    kreg = None
    if attribute_kernels:
        try:
            from ..ops.pallas import registry as kreg
        except Exception:  # kernel layer unavailable: audit still works
            kreg = None

    report = AuditReport()
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    by_prim: dict[str, dict] = {}
    by_coll: dict[str, dict] = {}
    intended = np.dtype(intended_dtype) if intended_dtype is not None else None

    attributed = {}  # id(eqn) -> (kernel_name, KernelCost)

    def _skip_inner(eqn):
        if kreg is None or eqn.primitive.name != "pallas_call":
            return False
        attr = kreg.attribute_eqn(eqn)
        if attr is None:
            return False
        attributed[id(eqn)] = attr
        return True

    for eqn in _iter_eqns(jaxpr, _skip_inner):
        name = eqn.primitive.name
        attr = attributed.get(id(eqn))
        if attr is not None:
            kname, cost = attr
            row = by_prim.setdefault(
                f"pallas::{kname}",
                {"primitive": f"pallas::{kname}", "count": 0, "flops": 0,
                 "bytes": 0})
            row["count"] += 1
            row["flops"] += cost.flops
            row["bytes"] += cost.bytes
            continue
        row = by_prim.setdefault(
            name, {"primitive": name, "count": 0, "flops": 0, "bytes": 0})
        row["count"] += 1
        row["flops"] += _eqn_flops(eqn)
        row["bytes"] += _byte_cost(eqn)

        if name in COLLECTIVE_PRIMS:
            # roofline comm side: payload = operand bytes (what crosses
            # the axis); feeds the same table shape as the HLO extractor
            crow = by_coll.setdefault(
                name, {"op": name, "count": 0, "payload_bytes": 0})
            crow["count"] += 1
            crow["payload_bytes"] += sum(
                _aval_bytes(v.aval) for v in eqn.invars
                if hasattr(v, "aval"))

        if name in HOST_TRANSFER_PRIMS:
            report.findings.append(Finding(
                get_rule("MX501"),
                f"primitive '{name}' performs a host round-trip inside "
                f"the compiled program", node=name))

        if intended is not None and name not in ("dot_general",
                                                 "conv_general_dilated"):
            in_dts = [v.aval.dtype for v in eqn.invars
                      if hasattr(v, "aval") and hasattr(v.aval, "dtype")]
            for ov in eqn.outvars:
                dt = getattr(ov.aval, "dtype", None)
                if dt is None or not _is_float(dt):
                    continue
                if dt.itemsize > intended.itemsize and any(
                        d == intended for d in in_dts):
                    report.findings.append(Finding(
                        get_rule("MX502"),
                        f"'{name}' promotes {intended} input(s) to {dt} "
                        f"(shape {tuple(getattr(ov.aval, 'shape', ()))})",
                        node=name))
                    break

    report.rows = sorted(by_prim.values(),
                         key=lambda r: r["bytes"], reverse=True)
    report.comm_rows = sorted(by_coll.values(),
                              key=lambda r: r["payload_bytes"], reverse=True)
    report.totals = {
        "flops": sum(r["flops"] for r in report.rows),
        "bytes": sum(r["bytes"] for r in report.rows),
        "eqns": sum(r["count"] for r in report.rows),
        "comm_payload_bytes": sum(r["payload_bytes"]
                                  for r in report.comm_rows),
    }
    return report


def audit_executor(executor, is_train=False,
                   intended_dtype=None) -> AuditReport:
    """Trace a bound Executor's forward program and audit its jaxpr.

    Uses the same graph-function builder the executor jits, so the audit
    sees exactly the program that runs (fusion plan, remat blocks and
    all)."""
    import jax
    import jax.numpy as jnp

    from ..executor import _build_graph_fn

    fn = _build_graph_fn(executor._symbol, is_train)
    arg_vals = {n: a._data for n, a in executor.arg_dict.items()}
    aux_vals = {n: a._data for n, a in executor.aux_dict.items()}
    rng = jnp.zeros((2,), jnp.uint32)
    closed = jax.make_jaxpr(fn)(arg_vals, aux_vals, rng)
    return audit_jaxpr(closed, intended_dtype=intended_dtype)


def cost_rows(fn, *example_args, intended_dtype=None,
              attribute_kernels=True):
    """Per-primitive FLOP/byte rows for an arbitrary traceable callable:
    the pre-fusion jaxpr's side of a cross-check against HLO-level
    accounting. Registered Pallas kernels land as ``pallas::<name>`` rows
    priced by the kernel registry."""
    import jax

    closed = jax.make_jaxpr(fn)(*example_args)
    report = audit_jaxpr(closed, intended_dtype=intended_dtype,
                         attribute_kernels=attribute_kernels)
    return report.rows, report.totals
