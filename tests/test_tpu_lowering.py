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

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import mxnet_tpu as mx
from mxnet_tpu import comm
from mxnet_tpu.ops import pallas as pk
from mxnet_tpu.ops.pallas import comm_kernels as ck

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def _latent_attention_case(seq):
    """Latent attention at the published widths
    (``kanana2_30b_a3b.seq8k``): ``seq`` positions, 32 heads whose queries
    and keys are 192 wide (128 of their own and the one rotary key of 64
    that every head reads) and whose values are 128, the rows read by the
    kernels as the projections leave them. Forward and backward: at 8,192
    positions one backward kernel, at 32,768 the two (a pair's dq is over
    the one kernel's VMEM budget)."""
    def build(S):
        from mxnet_tpu.ops import OPS

        op = OPS.create("LatentAttention", seq_len=seq, num_heads=32,
                        qk_nope_head_dim=128, qk_rope_head_dim=64,
                        v_head_dim=128, rope_theta=1e6)

        def loss(q, kv, k_pe):
            return jnp.sum(op.fwd([q, kv, k_pe], [], True, None)[0][0]
                           .astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1, 2)), (
            S((seq, 32 * 192), jnp.bfloat16), S((seq, 32 * 256), jnp.bfloat16),
            S((seq, 64), jnp.bfloat16))
    return build


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
    "flash": (_flash_case, {"flash_fwd", "flash_bwd_dkv"}),
    "flash_decoder_full": (_decoder_attention_case(48, 0, True),
                           {"flash_fwd", "flash_bwd_dkv"}),
    "flash_decoder_window": (_decoder_attention_case(64, 512, False),
                             {"flash_fwd", "flash_bwd_dkv"}),
    "flash_blockdiff": (_blockdiff_attention_case,
                        {"flash_fwd", "flash_bwd_dkv"}),
    "flash_latent": (_latent_attention_case(8192),
                     {"flash_fwd", "flash_bwd_dkv"}),
    # a pair's dq at 32,768 positions is over the one kernel's budget
    "flash_latent_32k": (_latent_attention_case(32768),
                         {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    "flash_padded_causal": (_padded_flash_case(True, None),
                            {"flash_fwd", "flash_bwd_dkv"}),
    "flash_padded_window": (_padded_flash_case(True, 512),
                            {"flash_fwd", "flash_bwd_dkv"}),
    "flash_padded_unmasked": (_padded_flash_case(False, None),
                              {"flash_fwd", "flash_bwd_dkv"}),
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


# -- the train step's state in the order its program reads it ---------------------

def _two_expert_layers():
    """Two ``MixtureOfExperts`` layers of 8 experts of 512 on rows of
    1,024 (stacked leaves of (8, 512, 1024) and (8, 1024, 512)), each
    behind a recomputation boundary as in the decoders, Adam, bfloat16
    compute: the shapes are small, the program's form is the cells'."""
    from mxnet_tpu import symbol as sym

    x = sym.Variable("data")
    for l in range(2):
        y = sym.MixtureOfExperts(
            data=sym.RMSNorm(data=x, eps=1e-6, name=f"layer{l}_norm"),
            name=f"layer{l}_moe", num_experts=8, experts_held=8, top_k=2,
            expert_width=512, train_router=False)
        x = sym.RematBoundary(
            data=sym._Plus(lhs=x, rhs=y, name=f"layer{l}_add"),
            name=f"layer{l}_out")
    head = sym.FullyConnected(data=x, num_hidden=1024, no_bias=True,
                              name="head")
    model = mx.FeedForward(sym.SoftmaxOutput(data=head, name="softmax"),
                           ctx=mx.cpu(), optimizer="adam",
                           learning_rate=1e-5, compute_dtype="bfloat16")
    return model, {"data": (2048, 1024), "softmax_label": (2048,)}


def _small_convnet():
    """A bottleneck network of two stages on 64 x 64 images, NHWC,
    bfloat16, SGD with momentum: 3 x 3 weights and their momentum, as the
    convnet cells hold them."""
    model = mx.FeedForward(
        mx.models.resnet((1, 1), num_classes=16, filter_list=(64, 128),
                         layout="NHWC"),
        ctx=mx.cpu(), optimizer="sgd", learning_rate=1e-3, momentum=0.9,
        compute_dtype="bfloat16")
    return model, {"data": (32, 64, 64, 3), "softmax_label": (32,)}


STATE_CASES = {"expert_layers": _two_expert_layers,
               "convnet": _small_convnet}


def _train_step(v5e, model, shapes, dtypes=None):
    """``FeedForward``'s own train step for ``shapes`` and its arguments
    described on the compile-only chip: ``(run, names, optimizer, metric,
    params, args)``."""
    names, aux_names = model._init_params(shapes)
    optimizer = model._resolve_optimizer(names, shapes["data"][0])
    metric = mx.metric.create("ce")
    run = model._get_train_step(None, ["data"], ["softmax_label"],
                                optimizer, None, metric=metric)
    order = model._state_order()

    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=v5e)

    params = {k: S([model.arg_params[k].shape[a] for a in
                    order.get(k, range(model.arg_params[k].ndim))])
              for k in names}
    aux = {k: S(model.aux_params[k].shape) for k in aux_names}
    opt_state = jax.tree_util.tree_map(
        lambda s: S(s.shape, s.dtype),
        jax.eval_shape(optimizer.init_state_tree, params))
    args = (params, opt_state, aux,
            {k: S(s, (dtypes or {}).get(k, jnp.float32))
             for k, s in shapes.items()}, S((2,), jnp.uint32),
            S(()), jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype),
                                          metric.device_init()))
    return run, names, optimizer, metric, params, args


@pytest.mark.parametrize("case", sorted(STATE_CASES))
def test_train_step_reads_its_state_where_it_lies(v5e, case, monkeypatch):
    """``FeedForward``'s own step builder, compiled for v5e: the stacked
    expert weights and their Adam moments cross the jit boundary in the
    order the grouped product reads them (``_stored_order``; the arrays'
    layouts stay the default), so the optimized HLO holds no float32
    ``copy`` of a stacked ``params[...]`` / ``opt_state[...]`` leaf (they
    were 12 a stacked matrix and step), every parameter and state leaf
    comes back in the layout it went in with, and one train program
    exists. A convnet's leaves are stored as declared."""
    import re

    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "0")
    model, shapes = STATE_CASES[case]()
    run, names, optimizer, metric, params, args = _train_step(
        v5e, model, shapes)
    order = model._state_order()
    tracked = run._tracked
    compiled = tracked.precompile(*args)
    ins, outs = compiled.input_formats[0], compiled.output_formats
    assert ins[0] == outs[0] and ins[1] == outs[1]
    copies = [line for line in compiled.as_text().splitlines()
              if re.search(r"= f32\[\d+,\d+,\d+\]\{[^}]*\} copy\(", line)
              and re.search(r"params\W|opt_state\W", line)]
    assert not copies, copies[:3]
    if case == "expert_layers":
        # gate and up of both layers; down follows them by itself
        assert sorted(order) == sorted(
            k for k in names if k.endswith(("gate_weight", "up_weight")))
        assert len(order) == 4 and all(
            params[k].shape == (8, 1024, 512) for k in order)
    else:
        assert not order
    # a second warm-up finds the program there; a second fit, the one handle
    tracked.precompile(*args)
    assert tracked.aot_programs == 1 and len(model._train_fns) == 1
    assert run is model._get_train_step(None, ["data"], ["softmax_label"],
                                        optimizer, None, metric=metric)


def test_latent_attention_runs_under_its_operators_scope(v5e, monkeypatch):
    """A two-layer ``kanana2`` (latent attention of the published head
    widths, 192 / 128, and a biased expert layer) through ``FeedForward``'s
    own step builder, compiled for v5e: the flash kernels of every layer
    (the forward, and the one backward kernel that makes every gradient at
    this length) carry ``layer<l>_attn/LatentAttention``, the scope the
    benchmark's readers look under (``benchmark/latent_costs.py``), and
    the forward kernel is not run again in the backward pass. The kernels
    read the rows as projected: under that scope the optimized HLO holds
    no ``transpose`` and no ``broadcast`` of an array the size of the
    queries, the keys and values, or the output (the one rotary key, 64
    columns, and its tables are all that XLA moves there)."""
    import math
    import re
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import scopes

    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "0")
    model = mx.FeedForward(
        mx.models.kanana2(seq_len=1024, layers=2, vocab_rows=512,
                          experts_held=4, n_routed_experts=16,
                          hidden_size=256, intermediate_size=512,
                          num_attention_heads=2, kv_lora_rank=128,
                          moe_intermediate_size=128, num_experts_per_tok=2,
                          train_router=False),
        ctx=mx.cpu(), optimizer="adam", learning_rate=1e-5,
        compute_dtype="bfloat16")
    shapes = {"data": (1, 1024), "softmax_label": (1, 1024)}
    run, *_, args = _train_step(v5e, model, shapes, dict.fromkeys(
        shapes, jnp.int32))
    text = run._tracked.precompile(*args).as_text()
    found = scopes.hlo_scopes(text)
    flash = sorted(v for k, v in found.items() if k.startswith("flash_"))
    pattern = r"layer([01])_attn/LatentAttention\)?/(flash_\w+)/pallas_call"
    assert sorted(re.search(pattern, v).groups() for v in flash) == [
        (l, k) for l in "01"
        for k in ("flash_bwd_dkv", "flash_fwd")]
    # the output (1,024 x 2 heads x 128) is the smallest of the three
    smallest = 1024 * 2 * 128
    moved = []
    for line in text.splitlines():
        op = re.search(r"= (\w+)\[([\d,]*)\]\{?[^ ]* (transpose|broadcast)\(",
                       line)
        if op and "LatentAttention" in line and math.prod(
                int(x) for x in op.group(2).split(",") if x) >= smallest:
            moved.append(line.strip()[:160])
    assert not moved, moved[:3]


def test_a_latent_layer_lowers_to_a_bounded_program(v5e, monkeypatch):
    """The set-up cost of the latent kernels, held off the chip: the
    ``flash_latent`` case (one cell-shaped layer, forward and gradients)
    lowers to at most 1.5 x the 387,600 characters of StableHLO text that
    the heads-first kernels before it lowered to, and its kernel bodies
    trace to no more equations than those kernels' 889. A kernel
    body is unrolled for every tile pattern, strip and piece and traced and
    lowered again in every layer, and the train step's lowering time
    follows the equations: a body that grows a product, a loop over heads
    or a division a piece fails here instead of in ``setup_s`` on the
    chip. Measured (jax 0.9.0): 83,336 characters (their locations' paths
    make a few thousand) and 726 equations (forward 358, the one backward
    kernel 368); with the backward in two kernels 93,214 and 823 (358 /
    221 / 244); the heads-first kernels 389,355 and 889; a pair walked by
    sub-tiles of 256 in the two backward kernels 1,073, and of 128 in the
    forward too 1,367."""
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "0")

    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    fn, args = _latent_attention_case(8192)(S)
    text = jax.jit(fn).lower(*args).as_text()
    assert text.count("@tpu_custom_call") == 2
    assert len(text) <= 1.5 * 387_600, len(text)

    def equations(jaxpr, inside=False):
        n = 0
        for eqn in jaxpr.eqns:
            n += inside
            kernel = eqn.primitive.name == "pallas_call"
            for p in eqn.params.values():
                for sub in p if isinstance(p, (tuple, list)) else (p,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        n += equations(sub, inside or kernel)
        return n

    assert equations(jax.make_jaxpr(fn)(*args).jaxpr) <= 889


def test_a_mamba2_layer_compiles_for_v5e_within_its_temporaries(v5e):
    """The chunked scan at the cell's widths (``nemotron3_nano_30b_a3b``:
    64 heads of 64, 8 groups of 128, chunks of 128, 8,192 positions,
    bfloat16), forward and the gradient of every input, compiles for v5e
    and plans at most 2.5 GB of temporaries (1.96 GB measured, jax 0.9.0;
    the decay planes alone are (chunk, chunk) float32 a head and chunk,
    268 MB each), which the cell's 16 GB holds beside 10.67 GB of
    state."""
    from mxnet_tpu.ops import OPS

    op = OPS.create("Mamba2Mixer", seq_len=8192, num_heads=64, head_dim=64,
                    n_groups=8, state_size=128, conv_kernel=4,
                    chunk_size=128)
    shapes, _, aux = op.infer_shape([(8192, 6144)] + [None] * 8)
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=v5e)
            for s in shapes]
    record = jax.ShapeDtypeStruct(aux[0], jnp.float32, sharding=v5e)

    def loss(ins, record):
        (out,), new = op.fwd(list(ins), [record], True, None)
        return jnp.sum(out.astype(jnp.float32) ** 2), new

    compiled = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        args, record).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= 2.5e9
