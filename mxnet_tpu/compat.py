"""The one place JAX APIs with a history of moving are imported from.

Motivation (ISSUE 1): the seed pinned an import path for ``shard_map``
that a JAX release moved — one moved symbol bricked all 75 test modules
at collection time. Call sites import such names from here, and direct
imports of the fragile paths are banned by the mxlint rule MX101
(``mxnet_tpu/analysis/source_lint.py``), so the next move is a one-line
fix. The code is written for the one installed JAX (pinned in
pyproject.toml): there are no branches for other versions.

Keep this module dependency-light: it is imported by models/parallel at
module scope, so anything heavy here taxes every ``import mxnet_tpu``.
"""

from __future__ import annotations

import jax
from jax import shard_map  # mxlint: disable=MX101

__all__ = ["shard_map", "distributed_initialized"]


def distributed_initialized() -> bool:
    """True when the jax.distributed runtime is up."""
    return bool(jax.distributed.is_initialized())
