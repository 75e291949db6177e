"""Shared plumbing for every Pallas kernel in this package.

One gate, one place: ``use_interpret()`` decides whether a kernel runs as
a compiled Mosaic program or through the Pallas interpreter. A process
that holds a TPU always compiles: there the interpreter would replace the
kernel under test with a slow XLA emulation that reports the same
results, so only an explicit ``interpret=True`` argument in code selects
it. Every other backend interprets, which is the unit-test path — the
SAME kernel code executes on the 8-device CPU mesh.
"""

from __future__ import annotations

import os

from ...base import ENV_OFF_VALUES, ENV_ON_VALUES, MXNetError

__all__ = ["use_interpret", "resolve_interpret"]


def use_interpret() -> bool:
    """Should Pallas kernels run under the interpreter in this process?

    Never on a TPU backend: ``MXNET_TPU_PALLAS_INTERPRET`` set truthy
    there raises instead of quietly swapping the kernel out. Off-TPU the
    variable overrides in both directions (falsy = build the Mosaic
    program anyway, e.g. to AOT-compile for a TPU topology from a CPU
    host); unset, kernels interpret.
    """
    import jax

    on_tpu = jax.default_backend() == "tpu"
    raw = os.environ.get("MXNET_TPU_PALLAS_INTERPRET", "").strip().lower()
    if raw in ENV_ON_VALUES:
        if on_tpu:
            raise MXNetError(
                "MXNET_TPU_PALLAS_INTERPRET is set but this process holds "
                "a TPU: kernels run compiled there; pass interpret=True "
                "to the one call you want to bisect")
        return True
    if raw in ENV_OFF_VALUES:
        return False
    return not on_tpu


def resolve_interpret(interpret) -> bool:
    """Normalize a kernel entry point's ``interpret=None`` default."""
    return use_interpret() if interpret is None else bool(interpret)
