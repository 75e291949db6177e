"""Kernel registry: every Pallas kernel declares its FLOP and byte model.

The jaxpr auditor (analysis/jaxpr_audit.py) prices ordinary primitives
from their avals, but a ``pallas_call`` is opaque to that arithmetic: its
inner jaxpr describes ONE grid cell, so recursing into it under-counts by
the grid size, and the eqn itself prices as an elementwise op. Before
this registry, flash attention's FLOPs were invisible to the MFU
accountant and the jaxpr cost table (the PR 5 under-counting this
module exists to close).

The contract (mshadow's kernel-template discipline, applied to cost):

  * every kernel module registers each ``pl.pallas_call`` it emits, keyed
    by the ``name=`` it passes to the call (mxlint MX312 flags modules
    that don't);
  * the model is a pure function of the call's FULL operand/result avals
    (shapes are trace-time constants, so the cost is exact arithmetic,
    never measurement);
  * the auditor attributes a registered ``pallas_call`` eqn from the
    model and does NOT descend into its inner jaxpr — one source of
    truth, no double counting. Unregistered kernels keep the legacy
    (under-counting) path so third-party pallas code never breaks an
    audit.

Registered costs also feed roofline rows (``analysis.jaxpr_audit.cost_rows``,
``telemetry.profiling``): achieved FLOP/s and bytes/s per kernel against
the machine peak.
"""

from __future__ import annotations

from dataclasses import dataclass

from ...analysis.lockwatch import named_lock

__all__ = ["KernelCost", "KernelSpec", "register_kernel", "get_kernel",
           "kernel_names", "kernels", "kernel_cost", "attribute_eqn",
           "catalog"]


@dataclass(frozen=True)
class KernelCost:
    """What one kernel invocation costs: model FLOPs (the mathematical
    requirement, the MFU-comparable number — not what the grid recomputes)
    and HBM bytes (every operand streamed in once, every result out once
    — the roofline's traffic floor)."""

    flops: float
    bytes: float

    @property
    def intensity(self) -> float:
        """Arithmetic intensity (FLOPs/byte) — which roofline slope the
        kernel lives under."""
        return self.flops / self.bytes if self.bytes else float("inf")


@dataclass(frozen=True)
class KernelSpec:
    """One registered kernel: ``cost_fn(in_avals, out_avals) ->
    KernelCost`` over the pallas_call's FULL (pre-blocking) avals."""

    name: str
    cost_fn: object
    doc: str = ""
    module: str = ""

    def cost(self, in_avals, out_avals) -> KernelCost:
        return self.cost_fn(in_avals, out_avals)


_LOCK = named_lock("ops.pallas.KernelRegistry")
_KERNELS: dict[str, KernelSpec] = {}


def register_kernel(name: str, cost_fn, doc: str = "",
                    module: str = "") -> KernelSpec:
    """Register (or idempotently re-register) a kernel's cost model.

    ``cost_fn(in_avals, out_avals)`` receives the pallas_call's full
    operand/result avals (objects with ``.shape``/``.size``/``.dtype``)
    and returns a :class:`KernelCost`. Called at kernel-module import;
    re-import overwrites in place (same name, same module)."""
    spec = KernelSpec(str(name), cost_fn, doc=doc, module=module)
    with _LOCK:
        _KERNELS[spec.name] = spec
    return spec


def get_kernel(name: str):
    with _LOCK:
        return _KERNELS.get(str(name))


def kernel_names():
    with _LOCK:
        return sorted(_KERNELS)


def kernels():
    with _LOCK:
        return dict(_KERNELS)


def _aval_nbytes(aval) -> int:
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0
    return int(size) * dtype.itemsize


def io_bytes(in_avals, out_avals) -> float:
    """The default byte model: stream every operand in and every result
    out exactly once (what a well-blocked kernel achieves; the roofline
    floor)."""
    return float(sum(_aval_nbytes(a) for a in in_avals)
                 + sum(_aval_nbytes(a) for a in out_avals))


def kernel_cost(name: str, in_avals, out_avals):
    """Cost of one invocation of a registered kernel, or None."""
    spec = get_kernel(name)
    if spec is None:
        return None
    return spec.cost(in_avals, out_avals)


def _pallas_call_name(params: dict):
    """The ``name=`` a pallas_call was emitted with, across jax versions
    (0.4.3x carries it inside ``name_and_src_info``)."""
    nsi = params.get("name_and_src_info")
    if nsi is not None and getattr(nsi, "name", None):
        return nsi.name
    return params.get("name")


def attribute_eqn(eqn):
    """``(kernel_name, KernelCost)`` for a ``pallas_call`` jaxpr eqn whose
    name is registered, else None (the auditor's hook). Never raises —
    a cost-model bug must not fail an audit."""
    if eqn.primitive.name != "pallas_call":
        return None
    name = _pallas_call_name(eqn.params)
    spec = get_kernel(name) if name else None
    if spec is None:
        return None
    try:
        ins = [v.aval for v in eqn.invars if hasattr(v, "aval")]
        outs = [v.aval for v in eqn.outvars]
        return name, spec.cost(ins, outs)
    except Exception:
        return None


def catalog() -> list:
    """Doc/bench rows: ``[{"kernel", "module", "doc"}, ...]`` sorted by
    name — the kernel catalog (doc/developer-guide/kernels.md)."""
    with _LOCK:
        return [{"kernel": s.name, "module": s.module, "doc": s.doc}
                for _, s in sorted(_KERNELS.items())]
