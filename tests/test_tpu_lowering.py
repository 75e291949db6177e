"""Every registered Pallas kernel compiles for the chip — checked without one.

A compile-only ``v5e:2x2`` topology (no TPU attached: nothing executes, so
this cannot take the real chip on a chip host either) is the target of an
AOT ``jit(...).lower(...).compile()`` per kernel at one representative
shape. The executable must contain a Mosaic custom call: a kernel the TPU
lowering refuses (block shapes off the (8, 128) tile, an unsupported
matmul mode) fails here, in the sandbox, instead of on the first chip run.
``chip_smoke.py`` is the other half: the same kernels, executed on the
chip, against their references.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import mxnet_tpu as mx
from mxnet_tpu import comm
from mxnet_tpu.ops import pallas as pk
from mxnet_tpu.ops.pallas import comm_kernels as ck


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this environment
        reason = f"compile-only v5e:2x2 topology unavailable: {e!r}"
        print(reason)
        pytest.skip(reason)
    return SingleDeviceSharding(topo.devices[0])


def _mosaic_kernels(fn, *args):
    """AOT-compile and return the names of the Mosaic kernels inside."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    return {name for name in pk.kernel_names() if name in text}


def _adam_case(S):
    opt = mx.optimizer.create("adam", learning_rate=1e-3, wd=1e-4)
    shapes = {"w": (300, 100), "b": (100,), "s": ()}
    p = {k: S(s) for k, s in shapes.items()}
    st = {k: (S(s), S(s), S(())) for k, s in shapes.items()}
    return (lambda p, g, s: pk.fused_adam_apply(opt, p, g, s, 1e-3,
                                                interpret=False)), (p, p, st)


def _flash_case(S):
    q = S((1, 2, 2048, 64), jnp.bfloat16)

    def loss(q, k, v):
        o = pk.flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2)), (q, q, q)


def _decoder_attention_case(heads, window, yarn):
    """The attention operator of the sparse decoder at its published
    widths (``laguna_xs2.seq8k``): 8,192 positions, heads of 128 over 8
    key-value heads, the window of 512 or none, the kernels rotating the
    queries and applying the head gate on rows as the projections leave
    them. Forward and both backward kernels."""
    def build(S):
        from mxnet_tpu.ops import OPS

        rope = dict(rotary_dim=64, rope_theta=500000.0, rope_type="yarn",
                    rope_factor=64.0, rope_beta_fast=64.0,
                    rope_attention_factor=1.4158883083359672) if yarn \
            else dict(rotary_dim=128)
        op = OPS.create("RotaryAttention", seq_len=8192, num_heads=heads,
                        num_kv_heads=8, head_dim=128, window=window,
                        gated=True, **rope)
        q = S((8192, heads * 128), jnp.bfloat16)
        kv = S((8192, 8 * 128), jnp.bfloat16)

        def loss(q, k, v, g):
            return jnp.sum(op.fwd([q, k, v, g], [], True, None)[0][0]
                           .astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1, 2, 3)), \
            (q, kv, kv, S((8192, heads), jnp.bfloat16))
    return build


def _blockdiff_attention_case(S):
    """The attention operator of block-diffusion training at the published
    widths (``sdar_30b_a3b.blockdiff4k``): a noisy and a clean copy of
    4,096 positions (8,192 rows), 32 heads of 128 over 4, blocks of 4, the
    kernels rotating the queries on rows as the projections leave them.
    Forward and both backward kernels, every quadrant's tiles."""
    from mxnet_tpu.ops import OPS

    op = OPS.create("BlockDiffusionAttention", seq_len=4096, block_length=4,
                    num_heads=32, num_kv_heads=4, head_dim=128,
                    rotary_dim=128, rope_theta=1e6)
    q = S((8192, 32 * 128), jnp.bfloat16)
    kv = S((8192, 4 * 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(op.fwd([q, k, v], [], True, None)[0][0]
                       .astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2)), (q, kv, kv)


def _padded_flash_case(causal, window):
    """The edge-tile variants that 8,192 positions never reach: 8,000
    positions of the cell's heads (128 wide, 16 over 8, rows as projected)
    leave a padded last key block, crossed by the diagonal, by the
    window's edge too, or by neither."""
    def build(S):
        q = S((1, 8000, 16, 128), jnp.bfloat16)
        kv = S((1, 8000, 8, 128), jnp.bfloat16)

        def loss(q, k, v):
            o = pk.flash_attention(q, k, v, causal=causal, window=window,
                                   heads_last=True, interpret=False)
            return jnp.sum(o.astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1, 2)), (q, kv, kv)
    return build


def _expert_space_case(S):
    """The expert layer's kernels at the widths of ``laguna_xs2.seq8k``:
    8,192 rows, top-8, hidden 2,048, bfloat16; each kernel both ways it
    is used (the gather with and without a scale, the sum with and
    without weights)."""
    from mxnet_tpu.ops.pallas.moe import moe_combine, moe_dispatch

    rows, k, width = 8192, 8, 2048

    def both(x, y, token, n, scale):
        return (moe_dispatch(x, token, n, interpret=False),
                moe_dispatch(x, token, n, scale, interpret=False),
                moe_combine(y, token, n, rows, interpret=False),
                moe_combine(y, token, n, rows, scale, interpret=False))

    return both, (S((rows, width), jnp.bfloat16),
                  S((rows * k, width), jnp.bfloat16),
                  S((rows * k,), jnp.int32), S((), jnp.int32),
                  S((rows * k,)))


def _quant_case(mode):
    def build(S):
        spec = comm.CompressionSpec(mode)
        return (lambda x: ck.fused_quantize(spec, x, want_dequant=True,
                                            interpret=False)), \
            (S((4, 1 << 16)),)
    return build


def _dequant_case(mode, fn):
    def build(S):
        spec = comm.CompressionSpec(mode)
        length = 1 << 16
        pay = {"q": S((4, length), jnp.int8),
               "scale": S((4, length // spec.chunk))} if mode == "int8" \
            else {"q": S((4, length // 4), jnp.uint8)}
        return (lambda p: fn(spec, p, interpret=False)), (pay,)
    return build


def _int8_mm_case(S):
    return (lambda x, w: pk.int8_matmul(x, w, interpret=False)), \
        (S((256, 2048)), S((1000, 2048)))


CASES = {
    "flash": (_flash_case, {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    "flash_decoder_full": (_decoder_attention_case(48, 0, True),
                           {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    "flash_decoder_window": (_decoder_attention_case(64, 512, False),
                             {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    "flash_blockdiff": (_blockdiff_attention_case,
                        {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    "flash_padded_causal": (_padded_flash_case(True, None),
                            {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    "flash_padded_window": (_padded_flash_case(True, 512),
                            {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    "flash_padded_unmasked": (_padded_flash_case(False, None),
                              {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    "expert_space": (_expert_space_case, {"moe_dispatch", "moe_combine"}),
    "fused_adam": (_adam_case, {"fused_adam"}),
    "quant_int8": (_quant_case("int8"), {"quant_int8"}),
    "quant_twobit": (_quant_case("twobit"), {"quant_twobit"}),
    "dequant_int8": (_dequant_case("int8", ck.fused_dequant),
                     {"dequant_int8"}),
    "dequant_twobit": (_dequant_case("twobit", ck.fused_dequant),
                       {"dequant_twobit"}),
    "dequant_sum_int8": (_dequant_case("int8", ck.fused_dequant_sum),
                         {"dequant_sum_int8"}),
    "dequant_sum_twobit": (_dequant_case("twobit", ck.fused_dequant_sum),
                           {"dequant_sum_twobit"}),
    "int8_matmul": (_int8_mm_case, {"int8_matmul"}),
}


def test_cases_cover_the_registry():
    covered = set().union(*(names for _, names in CASES.values()))
    assert covered == set(pk.kernel_names())


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, case, monkeypatch):
    build, names = CASES[case]
    # an operator has no ``interpret`` argument: off the chip the gate
    # builds the Mosaic program only when told to
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "0")

    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    fn, args = build(S)
    found = _mosaic_kernels(fn, *args)
    assert names <= found, (case, names - found)
