"""mxnet_tpu.analysis — the mxlint static-analysis subsystem (ISSUE 1).

Three passes over three representations of the same program:

  Pass 1  source lint   (`source_lint`)  — AST walk over .py files:
          version-fragile JAX imports, host-sync hazards in traced code,
          recompilation risks. Pure AST work: linted files are never
          imported or traced.
  Pass 2  graph verify   (`graph`)       — ``Symbol.verify()``: full
          static shape *and dtype* inference over the node DAG plus
          structural checks, run automatically on every bind
          (reference: StaticGraph::InferShape).
  Pass 3  jaxpr audit    (`jaxpr_audit`) — inspects a bound executor's
          traced jaxpr for host transfers, dtype promotions, and per-op
          FLOP/byte totals (feeds the MFU accountant and the profiler's
          roofline rows).
  Pass 4  concurrency    (`concurrency`)  — whole-package model of thread
          entry points and lock scopes: shared-state races (MX701),
          lock-order cycles (MX702), bare cv.wait (MX703), leaked
          non-daemon threads (MX704), fresh-lock locking (MX705). The
          runtime complement is the lock-order watchdog (`lockwatch`,
          gate MXNET_TPU_LOCKWATCH): the repo's locks are built by its
          named factory, and enabling it records per-thread held-lock
          sets plus the global acquisition-order graph, reporting cycles
          and stalls as hub gauges and flight-recorder incidents.
  Pass 5  sharding audit (`sharding`)    — audits the LOWERED distributed
          program: the traced jaxpr (large replicated intermediates
          MX801, collectives inside scan/while bodies MX803) and the
          compiled HLO's collective set reconciled EXACTLY against the
          comm layer's closed-form plan (MX802 — every unplanned
          all-gather/all-to-all named), plus PartitionSpec sanity
          (MX804) and a source-level placement-discipline rule (MX805,
          rides with Pass 1). Wired three ways: the
          ``--shardcheck``/``--all`` CLI, the opt-in runtime gate
          ``fit/precompile(shard_audit=True)`` /
          ``MXNET_TPU_SHARD_AUDIT=1`` auditing the exact warmed
          program, and ``--ci``/``--baseline`` structured rows with
          exit 3 on new violations.

Rules live in a registry (`rules`) keyed by stable ids (MX101, ...), each
with a severity and a fixit hint — adding a rule never touches a driver.
CLI: ``python -m mxnet_tpu.analysis [paths]`` (wrapped by
tools/run_mxlint.py; the self-lint gates the tier-1 suite via
tests/test_mxlint.py).

Suppression: ``# mxlint: disable=MX101`` on the offending line, or
``# mxlint: skip-file`` in the first five lines.
"""

from .rules import RULES, Finding, Rule, get_rule, register_rule
from .source_lint import lint_file, lint_paths, lint_source
from .graph import verify_json, verify_json_file, verify_symbol
from . import lockwatch

__all__ = [
    "RULES", "Finding", "Rule", "get_rule", "register_rule",
    "lint_file", "lint_paths", "lint_source",
    "verify_json", "verify_json_file", "verify_symbol",
    "audit_executor", "audit_jaxpr", "cost_rows", "main",
    "lockwatch", "concurrency_lint_paths", "concurrency_lint_source",
    "audit_step_program", "audit_collective_drift", "audit_jaxpr_sharding",
    "check_partition_specs", "expected_collectives", "selfcheck_report",
    "shard_audit_enabled",
]


def concurrency_lint_paths(paths):
    """Pass 4 over a file set (lazy import keeps the package light)."""
    from . import concurrency

    return concurrency.lint_paths(paths)


def concurrency_lint_source(text, path="<string>"):
    from . import concurrency

    return concurrency.lint_source(text, path)


def audit_executor(*args, **kwargs):
    """Lazy re-export: Pass 3 pulls in jax; keep the CLI import-light."""
    from .jaxpr_audit import audit_executor as impl

    return impl(*args, **kwargs)


def audit_jaxpr(*args, **kwargs):
    from .jaxpr_audit import audit_jaxpr as impl

    return impl(*args, **kwargs)


def cost_rows(*args, **kwargs):
    from .jaxpr_audit import cost_rows as impl

    return impl(*args, **kwargs)


def audit_step_program(*args, **kwargs):
    """Lazy re-export: Pass 5 pulls in jax; keep the CLI import-light."""
    from .sharding import audit_step_program as impl

    return impl(*args, **kwargs)


def audit_collective_drift(*args, **kwargs):
    from .sharding import audit_collective_drift as impl

    return impl(*args, **kwargs)


def audit_jaxpr_sharding(*args, **kwargs):
    from .sharding import audit_jaxpr_sharding as impl

    return impl(*args, **kwargs)


def check_partition_specs(*args, **kwargs):
    from .sharding import check_partition_specs as impl

    return impl(*args, **kwargs)


def expected_collectives(*args, **kwargs):
    from .sharding import expected_collectives as impl

    return impl(*args, **kwargs)


def selfcheck_report(*args, **kwargs):
    from .sharding import selfcheck_report as impl

    return impl(*args, **kwargs)


def shard_audit_enabled(*args, **kwargs):
    from .sharding import shard_audit_enabled as impl

    return impl(*args, **kwargs)


def main(argv=None) -> int:
    from .__main__ import main as impl

    return impl(argv)
