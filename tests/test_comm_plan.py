"""Inspect the SPMD partitioner's communication plan from compiled HLO.

The rig cannot run 8→256 real chips, but the compiler's
comm plan for a sharded train step is inspectable without hardware — the
collective ops in the optimized HLO ARE the wire plan. These tests compile
the flagship transformer train step over virtual meshes and assert the
expected collective *kinds* appear (and forbidden ones don't), rather than
brittle exact counts:

- dp-only: gradient sync must lower to all-reduce; nothing ring-shaped
  (no collective-permute) may appear.
- dp×tp: tensor-parallel activations add all-reduces (strictly more than
  dp-only) — the Megatron row/column pattern.
- dp×sp: ring attention must lower to collective-permute chains — at least
  (sp-1) permute steps per direction per layer — while the gradient sync
  all-reduce remains.

Runs on the 8-virtual-CPU-device mesh from conftest.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.models.transformer import TransformerLM, transformer_lm_config
from mxnet_tpu.parallel import make_mesh


def _compiled_hlo(dp, tp, sp, n_layers=2):
    n = dp * tp * sp
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    mesh = make_mesh(dp=dp, tp=tp, sp=sp, devices=jax.devices()[:n])
    cfg = transformer_lm_config(
        vocab_size=64, d_model=16, n_heads=max(2, 2 * tp),
        n_layers=n_layers, max_len=8 * max(1, sp), dtype=jnp.float32)
    model = TransformerLM(cfg)
    params, moms = model.init_sharded(mesh, seed=0)
    step = model.make_train_step(mesh, lr=0.1)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 64, (2 * dp, 8 * sp)).astype(np.int32)
    lowered = jax.jit(step).lower(params, moms, tokens, tokens)
    return lowered.compile().as_text()


def _count(hlo, opname):
    # count instruction definitions by OPCODE: "%anyname = <shape>
    # all-reduce(..." — instruction names follow the jax op name (e.g.
    # %ppermute.57 = ... collective-permute(...)), so match the opcode
    # token after the shape, incl. tuple shapes and async -start variants
    return len(re.findall(
        rf"=\s*(?:\([^)]*\)|\S+)\s+{opname}(?:-start)?\(", hlo))


def test_dp_only_plan_is_allreduce_no_permute():
    hlo = _compiled_hlo(dp=8, tp=1, sp=1)
    ar = _count(hlo, "all-reduce")
    cp = _count(hlo, "collective-permute")
    assert ar >= 1, "dp gradient sync must lower to all-reduce"
    assert cp == 0, f"dp-only plan must not contain ring permutes, got {cp}"


def test_tp_adds_activation_allreduces():
    hlo_dp = _compiled_hlo(dp=4, tp=1, sp=1)
    hlo_tp = _compiled_hlo(dp=2, tp=2, sp=1)
    ar_dp = _count(hlo_dp, "all-reduce")
    ar_tp = _count(hlo_tp, "all-reduce")
    assert ar_tp > ar_dp, (
        f"Megatron tp must add activation all-reduces: dp-only={ar_dp}, "
        f"dp*tp={ar_tp}")


def test_sp_ring_lowers_to_collective_permute():
    n_layers = 2
    sp = 2
    hlo = _compiled_hlo(dp=2, tp=1, sp=sp, n_layers=n_layers)
    cp = _count(hlo, "collective-permute")
    ar = _count(hlo, "all-reduce")
    # ring fwd rotates k and v (sp-1 steps); backward rotates again.
    # Floor: one permute step per layer per direction.
    assert cp >= 2 * n_layers * (sp - 1), (
        f"ring attention should emit >= {2 * n_layers * (sp - 1)} "
        f"collective-permutes, got {cp}")
    assert ar >= 1, "gradient sync all-reduce must still be present"


def test_comm_plan_reports_byte_sizes():
    """The plan is quantifiable: collective operand shapes are in the HLO,
    so bytes-on-the-wire per step is a checkable number (here: just assert
    we can extract a nonzero total for the dp gradient sync)."""
    hlo = _compiled_hlo(dp=8, tp=1, sp=1)
    total = 0
    for line in hlo.splitlines():
        m = re.search(r"=\s*(\([^)]*\)|\S+)\s+all-reduce(?:-start)?\(", line)
        if not m:
            continue
        for dims in re.findall(r"f32\[([\d,]*)\]", m.group(1)):
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            total += 4 * n
    assert total > 0, "could not extract all-reduce payload sizes from HLO"


def test_comm_subsystem_table_agrees_with_local_parse():
    """comm.hlo_collective_table generalizes this module's ad-hoc parsing
    (opcode counts + payload bytes + ring-factor wire bytes); the two must
    agree on the dp-only transformer plan."""
    from mxnet_tpu import comm

    hlo = _compiled_hlo(dp=8, tp=1, sp=1)
    table = {r["op"]: r for r in comm.hlo_collective_table(
        hlo, default_group_size=8)}
    assert "all-reduce" in table
    assert table["all-reduce"]["count"] == _count(hlo, "all-reduce")
    assert "collective-permute" not in table
    ar = table["all-reduce"]
    assert ar["payload_bytes"] > 0
    # ring all-reduce wire factor: 2*(n-1)/n of the payload
    assert ar["wire_bytes"] == pytest.approx(
        2 * 7 / 8 * ar["payload_bytes"], rel=1e-6)
    assert comm.hlo_collective_wire_bytes(hlo, 8) >= ar["wire_bytes"]


def test_sp_ring_permutes_counted_by_comm_table():
    from mxnet_tpu import comm

    hlo = _compiled_hlo(dp=2, tp=1, sp=2)
    table = {r["op"]: r for r in comm.hlo_collective_table(
        hlo, default_group_size=2)}
    assert table["collective-permute"]["count"] == \
        _count(hlo, "collective-permute")
    # permute wire = payload exactly (point-to-point)
    assert table["collective-permute"]["wire_bytes"] == \
        table["collective-permute"]["payload_bytes"]
