"""Pallas kernel layer tests (ISSUE 13 acceptance).

Covers: the shared interpret gate (+ env override), fused comm
quantize/dequantize bitwise wire parity vs the compression.py reference
codecs, the dp-8 exchange's HLO quantize-pass reduction with identical
collective wire bytes, fused-Adam/AdamW bitwise parity vs the per-leaf
optimizer (state layout unchanged, cross-path resume), int8 matmul error
bound + the Predictor serving path, the kernel registry's jaxpr/MFU
attribution (flash attention's FLOPs stop being invisible), and the
armed zero-recompile epoch with every kernel enabled.

Bitwise comparisons run both paths inside ONE jit: XLA's algebraic
rewrites (e.g. divide -> multiply-by-reciprocal on CPU) apply uniformly
within a program, which is exactly the context the kernels run in (the
fused train step) — eager-vs-jit is the comparison that isn't meaningful.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import mxnet_tpu as mx
import mxnet_tpu.optimizer as opt_mod
from mxnet_tpu import comm
from mxnet_tpu.analysis import jaxpr_audit
from mxnet_tpu.compat import shard_map
from mxnet_tpu.ops import pallas as pk
from mxnet_tpu.ops.pallas import comm_kernels as ck
from mxnet_tpu.ops.pallas.adam import fused_adam_apply
from mxnet_tpu.utils import compile as cm


def _mesh8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    return Mesh(np.array(devs[:8]), ("dp",))


def _ctx8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    return [mx.cpu(i) for i in range(8)]


def _blobs(n=160, d=10, k=4, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    y = rng.randint(0, k, n)
    X += (rng.randn(k, d) * 3.0)[y]
    return X.astype(np.float32), y.astype(np.int32)


def _mlp(hidden=64, classes=4):
    d = mx.symbol.Variable("data")
    h = mx.symbol.FullyConnected(d, num_hidden=hidden, name="fc1")
    h = mx.symbol.Activation(h, act_type="relu")
    h = mx.symbol.FullyConnected(h, num_hidden=classes, name="fc2")
    return mx.symbol.SoftmaxOutput(h, name="softmax")


# -- shared interpret gate -----------------------------------------------------

def test_interpret_gate_env_override(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_PALLAS_INTERPRET", raising=False)
    assert pk.use_interpret() == (jax.default_backend() != "tpu")
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    assert pk.use_interpret() is True
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "0")
    assert pk.use_interpret() is False
    assert pk.resolve_interpret(True) is True
    assert pk.resolve_interpret(None) is False  # env still forces compiled
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "on")
    assert pk.resolve_interpret(None) is True


def test_flash_attention_uses_shared_gate():
    # the hoisted helper is the one flash consults (satellite: no more
    # module-local default_backend() read)
    import importlib

    # the package re-exports the flash_attention FUNCTION under the
    # module's name, so resolve the module through importlib
    fa = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
    from mxnet_tpu.ops.pallas import _common

    assert fa._use_interpret is _common.use_interpret


# -- fused comm kernels: bitwise wire parity -----------------------------------

@pytest.mark.parametrize("mode,length", [("int8", 2048), ("twobit", 2048),
                                         ("twobit", 1028)])
def test_fused_quantize_bitwise_wire_parity(mode, length):
    """ACCEPTANCE: kernel payload == reference codec payload, bit for
    bit, for every wire array AND the error-feedback round-trip. 1028 is
    a twobit row the 512-lane kernel view has to pad."""
    spec = comm.CompressionSpec(mode, chunk=256)
    rng = np.random.RandomState(0)
    rows = jnp.asarray(rng.randn(8, length).astype(np.float32))

    @jax.jit
    def both(x):
        ref = comm.encode(spec, x)
        ref_dq = comm.decode(spec, ref)
        pay, dq = ck.fused_quantize(spec, x, want_dequant=True,
                                    block_elems=512)
        sum_ref = jnp.sum(comm.decode(spec, ref), axis=0)
        sum_k = ck.fused_dequant_sum(spec, pay, block_elems=512)
        dec_k = ck.fused_dequant(spec, pay, block_elems=512)
        return ref, ref_dq, pay, dq, sum_ref, sum_k, dec_k

    ref, ref_dq, pay, dq, sum_ref, sum_k, dec_k = both(rows)
    assert set(pay) == set(ref)
    for k in ref:
        assert pay[k].dtype == ref[k].dtype
        assert pay[k].shape == ref[k].shape
        assert (np.asarray(pay[k]) == np.asarray(ref[k])).all(), (mode, k)
    # the fused decode round-trip IS the codec's (residual basis bitwise)
    assert (np.asarray(dq) == np.asarray(ref_dq)).all()
    assert (np.asarray(dec_k) == np.asarray(ref_dq)).all()
    # the accumulate fuses the sum: values agree to reduction order
    np.testing.assert_allclose(np.asarray(sum_k), np.asarray(sum_ref),
                               rtol=1e-6, atol=1e-6)


def test_fused_quantize_1d_and_block_picking():
    spec = comm.CompressionSpec("int8", chunk=4)
    v = jnp.asarray(np.random.RandomState(1).randn(64).astype(np.float32))
    pay, dq = jax.jit(lambda x: ck.fused_quantize(spec, x,
                                                  want_dequant=True))(v)
    ref = comm.encode(spec, v)
    assert pay["q"].shape == ref["q"].shape == (64,)
    assert pay["scale"].shape == ref["scale"].shape == (16,)
    assert dq.shape == (64,)
    assert (np.asarray(pay["q"]) == np.asarray(ref["q"])).all()
    # block picking: whole rows under the cap, in 32-row (8-bit tile)
    # steps, never below one tile; everything in one block when it fits
    assert ck.rows_per_block(1000, 256, 65536) == 256
    assert ck.rows_per_block(1000, 256, 70 * 256) == 64
    assert ck.rows_per_block(1000, 256, 512) == 32
    assert ck.rows_per_block(20, 256, 512) == 20
    with pytest.raises(mx.base.MXNetError):
        ck.fused_quantize(spec, jnp.zeros((10,), jnp.float32))


def test_exchange_kernel_path_hlo_and_values():
    """ACCEPTANCE: on the dp-8 mesh the kernel path (a) removes EVERY
    full-slab quantize-shaped HLO pass the codec path runs, (b) moves
    byte-identical collectives, (c) produces the same reduced gradients
    and residuals (to reduction order)."""
    mesh = _mesh8()
    ndev = 8
    spec = comm.CompressionSpec("int8", chunk=256)
    L = ndev * 2048
    rng = np.random.RandomState(0)
    tree = {"g": jnp.asarray(rng.randn(L).astype(np.float32))}
    resid = jnp.asarray(rng.randn(ndev, L).astype(np.float32) * 0.01)

    def build(cfg):
        def body(t, r):
            return comm.error_feedback_allreduce(
                t, r, spec, axis_name="dp", axis_size=ndev, kernels=cfg)
        return jax.jit(shard_map(body, mesh=mesh,
                                 in_specs=(P(), P("dp")),
                                 out_specs=(P(), P("dp")),
                                 check_vma=False))

    f_ref = build(False)
    f_k = build(comm.CommKernelConfig(block_elems=512))
    hlo_ref = f_ref.lower(tree, resid).compile().as_text()
    hlo_k = f_k.lower(tree, resid).compile().as_text()

    passes_ref = comm.hlo_quantize_pass_count(hlo_ref, min_elements=L)
    passes_k = comm.hlo_quantize_pass_count(hlo_k, min_elements=L)
    assert passes_ref > 0
    assert passes_k == 0, (passes_k, passes_ref)

    wire_ref = sum(r["wire_bytes"] for r in
                   comm.hlo_collective_table(hlo_ref, default_group_size=8))
    wire_k = sum(r["wire_bytes"] for r in
                 comm.hlo_collective_table(hlo_k, default_group_size=8))
    assert wire_ref == wire_k > 0

    (out_ref, res_ref) = f_ref(tree, resid)
    (out_k, res_k) = f_k(tree, resid)
    # the fused accumulate's summation order is not the codec path's, so
    # a reduced value landing within an ulp of a round boundary can flip
    # one stage-2 quantization step — the difference is bounded by that
    # step (one scale unit) and must be RARE; the wire payloads of each
    # path against its own codec reference are bitwise (test above)
    o_ref, o_k = np.asarray(out_ref["g"]), np.asarray(out_k["g"])
    step = np.abs(o_ref).max() / 127.0
    diff = np.abs(o_k - o_ref)
    assert diff.max() <= step * 1.01, (diff.max(), step)
    assert (diff > step * 1e-3).mean() < 0.01  # full-step flips are rare
    r_diff = np.abs(np.asarray(res_k) - np.asarray(res_ref))
    assert r_diff.max() <= step * 1.01
    assert (r_diff > step * 1e-3).mean() < 0.01


def test_overlap_allreduce_kernel_path_matches_codec():
    """SATELLITE wiring: comm/overlap.py threads kernels= per bucket."""
    mesh = _mesh8()
    ndev = 8
    shapes = {"a": (64, 32), "b": (96,), "c": (32, 16)}
    plan = comm.plan_overlap(shapes, "int8", ndev, max_bytes=4096)
    rng = np.random.RandomState(2)
    tree = {k: jnp.asarray(rng.randn(*s).astype(np.float32))
            for k, s in shapes.items()}
    resid = comm.init_overlap_residuals(plan)

    def build(cfg):
        def body(t, r):
            return comm.overlap_allreduce(t, r, plan, axis_name="dp",
                                          kernels=cfg)
        rspec = {k: P("dp") for k in resid}
        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(), rspec),
            out_specs=(P(), rspec), check_vma=False))

    out_ref, res_ref = build(False)(tree, resid)
    out_k, res_k = build(comm.CommKernelConfig(block_elems=256))(tree, resid)
    # same bound as test_exchange_kernel_path_hlo_and_values: the fused
    # accumulate's sum order can flip one stage-2 quantization step
    step = max(float(np.abs(np.asarray(out_ref[k])).max())
               for k in tree) / 127.0
    for k in tree:
        d = np.abs(np.asarray(out_k[k]) - np.asarray(out_ref[k]))
        assert d.max() <= step * 1.01, (k, d.max(), step)
    for k in res_ref:
        d = np.abs(np.asarray(res_k[k]) - np.asarray(res_ref[k]))
        assert d.max() <= step * 1.01, (k, d.max(), step)


def test_comm_kernel_config_resolve(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_COMM_KERNELS", raising=False)
    assert comm.CommKernelConfig.resolve(None) is None
    assert comm.CommKernelConfig.resolve(False) is None
    assert comm.CommKernelConfig.resolve(True).block_elems is None
    assert comm.CommKernelConfig.resolve(4096).block_elems == 4096
    cfg = comm.CommKernelConfig(block_elems=512)
    assert comm.CommKernelConfig.resolve(cfg) is cfg
    monkeypatch.setenv("MXNET_TPU_COMM_KERNELS", "1")
    assert comm.CommKernelConfig.resolve(None) is not None
    monkeypatch.setenv("MXNET_TPU_COMM_KERNELS", "8192")
    assert comm.CommKernelConfig.resolve(None).block_elems == 8192
    monkeypatch.setenv("MXNET_TPU_COMM_KERNELS", "off")
    assert comm.CommKernelConfig.resolve(None) is None
    with pytest.raises(mx.base.MXNetError):
        comm.CommKernelConfig(block_elems=0)


# -- fused Adam/AdamW ----------------------------------------------------------

def test_fused_adam_bitwise_parity():
    """ACCEPTANCE: fused kernel == Adam._apply_one per leaf, bitwise on
    f32 — params AND both moments, with rescale/clip/L2-wd active."""
    rng = np.random.RandomState(1)
    shapes = {"w1": (64, 33), "b1": (33,), "w2": (7, 5), "s": ()}
    params = {n: jnp.asarray(np.asarray(rng.randn(*s), np.float32))
              for n, s in shapes.items()}
    grads = {n: jnp.asarray(np.asarray(rng.randn(*s), np.float32))
             for n, s in shapes.items()}
    opt = opt_mod.Adam(lr=0.01, wd=0.02, clip_gradient=0.5,
                       rescale_grad=1.0 / 32)
    states = opt.init_state_tree(params)

    @jax.jit
    def both(p, g, s, lr):
        ref = opt_mod.Optimizer.apply(opt, p, g, s, lr)
        fz = fused_adam_apply(opt, p, g, s, lr, block=64)
        return ref, fz

    for step in range(3):  # bias correction moves with t
        (rp, rs), (fp, fs) = both(params, grads, states, jnp.float32(0.01))
        for n in shapes:
            assert (np.asarray(rp[n]) == np.asarray(fp[n])).all(), (step, n)
            for i in range(3):
                assert (np.asarray(rs[n][i]) == np.asarray(fs[n][i])).all()
        params, states = rp, rs


def test_fused_adamw_decay_filter_parity():
    rng = np.random.RandomState(2)
    shapes = {"w1": (48, 16), "b1": (16,), "ln_scale": (16,)}
    params = {n: jnp.asarray(rng.randn(*s).astype(np.float32))
              for n, s in shapes.items()}
    grads = {n: jnp.asarray(rng.randn(*s).astype(np.float32))
             for n, s in shapes.items()}
    flt = lambda n: n.startswith("w")  # noqa: E731
    ref_opt = opt_mod.AdamW(weight_decay=0.05, decay_filter=flt,
                            fused=False)
    fz_opt = opt_mod.AdamW(weight_decay=0.05, decay_filter=flt, fused=True)
    states = ref_opt.init_state_tree(params)

    @jax.jit
    def both(p, g, s, lr):
        return ref_opt.apply(p, g, s, lr), fz_opt.apply(p, g, s, lr)

    (rp, rs), (fp, fs) = both(params, grads, states, jnp.float32(0.003))
    for n in shapes:
        assert (np.asarray(rp[n]) == np.asarray(fp[n])).all(), n
        for i in range(3):
            assert (np.asarray(rs[n][i]) == np.asarray(fs[n][i])).all()


def test_fused_adam_state_layout_and_cross_path_resume():
    """SATELLITE: fused-Adam state layout == tree_state layout (no
    checkpoint migration), and a trajectory may switch paths mid-run:
    fused steps then per-leaf steps == per-leaf throughout, bitwise."""
    rng = np.random.RandomState(3)
    shapes = {"a": (32, 8), "b": (8,)}
    params0 = {n: jnp.asarray(rng.randn(*s).astype(np.float32))
               for n, s in shapes.items()}
    fused = opt_mod.Adam(lr=0.01, fused=True)
    plain = opt_mod.Adam(lr=0.01, fused=False)
    s_f = fused.init_state_tree(params0)
    s_p = plain.init_state_tree(params0)
    assert jax.tree_util.tree_structure(s_f) == \
        jax.tree_util.tree_structure(s_p)

    def grad_of(i):
        r = np.random.RandomState(100 + i)
        return {n: jnp.asarray(r.randn(*shapes[n]).astype(np.float32))
                for n in shapes}

    run_f = jax.jit(lambda p, g, s: fused.apply(p, g, s, jnp.float32(0.01)))
    run_p = jax.jit(lambda p, g, s: plain.apply(p, g, s, jnp.float32(0.01)))

    pa, sa = params0, s_f
    for i in range(2):
        pa, sa = run_f(pa, grad_of(i), sa)
    # state layout identical => the per-leaf path resumes it directly
    assert jax.tree_util.tree_structure(sa) == \
        jax.tree_util.tree_structure(s_p)
    for i in range(2, 4):
        pa, sa = run_p(pa, grad_of(i), sa)

    pb, sb = params0, s_p
    for i in range(4):
        pb, sb = run_p(pb, grad_of(i), sb)
    for n in shapes:
        assert (np.asarray(pa[n]) == np.asarray(pb[n])).all(), n
        for i in range(3):
            assert (np.asarray(sa[n][i]) == np.asarray(sb[n][i])).all()


def test_fused_adam_env_gate(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_FUSED_ADAM", raising=False)
    assert not opt_mod.Adam()._fused_active()
    assert opt_mod.Adam(fused=True)._fused_active()
    monkeypatch.setenv("MXNET_TPU_FUSED_ADAM", "1")
    assert opt_mod.Adam()._fused_active()
    assert not opt_mod.Adam(fused=False)._fused_active()


# -- int8 matmul ---------------------------------------------------------------

def test_int8_matmul_error_bound_and_shapes():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(37, 100).astype(np.float32))
    w = jnp.asarray(rng.randn(23, 100).astype(np.float32))
    y = pk.int8_matmul(x, w, block_m=16, block_n=16)
    ref = x @ w.T
    assert y.shape == (37, 23) and y.dtype == jnp.float32
    err = float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref))
    assert err < 2e-2, err
    # pre-quantized weights path
    wq, sw = pk.quantize_channels(w)
    y2 = pk.int8_matmul(x, wq, w_scale=sw, block_m=16, block_n=16)
    assert (np.asarray(y2) == np.asarray(y)).all()
    with pytest.raises(ValueError):
        pk.int8_matmul(x, wq)


def test_predictor_int8_quantize_serving_path():
    """SATELLITE wiring: Predictor(quantize='int8') serves FC matmuls
    through the kernel — close to f32, and actually quantized."""
    from mxnet_tpu.predictor import Predictor

    X, y = _blobs(96)
    model = mx.FeedForward(_mlp(hidden=32), ctx=mx.cpu(), num_epoch=3,
                           learning_rate=0.5)
    model.fit(X, y, batch_size=32)
    args = {k: v for k, v in model.arg_params.items()}
    p32 = Predictor(model.symbol, args, model.aux_params)
    p8 = Predictor(model.symbol, args, model.aux_params, quantize="int8")
    out32 = p32.forward(data=X[:32]).get_output(0)
    out8 = p8.forward(data=X[:32]).get_output(0)
    np.testing.assert_allclose(out8, out32, rtol=0.1, atol=0.05)
    assert not (out8 == out32).all()  # the quantized program really ran
    assert (out8.argmax(axis=1) == out32.argmax(axis=1)).mean() > 0.9
    with pytest.raises(mx.base.MXNetError):
        Predictor(model.symbol, args, quantize="int4")


# -- kernel registry + jaxpr/MFU attribution -----------------------------------

def test_registry_catalog_covers_all_kernels():
    names = set(pk.kernel_names())
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
            "quant_int8", "quant_twobit", "dequant_sum_int8",
            "dequant_sum_twobit", "dequant_int8", "dequant_twobit",
            "fused_adam", "int8_matmul"} <= names
    cat = pk.catalog()
    assert all(r["doc"] and r["module"].startswith("mxnet_tpu.ops.pallas")
               for r in cat)


def test_jaxpr_audit_attributes_flash_flops():
    """SATELLITE: transformer-shaped forward with flash attention — the
    registry-attributed FLOP total strictly exceeds the unattributed
    baseline on the SAME trace, so MFU strictly increases (same peak,
    same wall time, bigger honest numerator)."""
    rng = np.random.RandomState(5)
    b, h, s, d = 2, 2, 128, 32
    q = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
    wo = jnp.asarray(rng.randn(h * d, h * d).astype(np.float32))

    def transformer_fwd(q, wo):
        attn = pk.flash_attention(q, q, q, causal=True,
                                  block_q=32, block_k=32)
        o = attn.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        return jnp.sum(o @ wo)

    closed = jax.make_jaxpr(transformer_fwd)(q, wo)
    with_reg = jaxpr_audit.audit_jaxpr(closed)
    without = jaxpr_audit.audit_jaxpr(closed, attribute_kernels=False)
    assert with_reg.totals["flops"] > without.totals["flops"]
    prows = {r["primitive"]: r for r in with_reg.rows
             if r["primitive"].startswith("pallas::")}
    assert "pallas::flash_fwd" in prows
    # the model: 4 * bh * sq * sk * d (padded dims here == logical dims)
    assert prows["pallas::flash_fwd"]["flops"] == 4 * b * h * s * s * d
    # baseline counted one grid cell at elementwise rates — the dense
    # matmul FLOPs must dominate it
    assert with_reg.totals["flops"] >= 4 * b * h * s * s * d


def test_mfu_accountant_counts_flash():
    """The PR 5 MFU path resolves FLOPs through the same audit — a flash
    program's flops_per_step now includes the attention FLOPs."""
    from mxnet_tpu.telemetry.mfu import MFUAccountant

    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(1, 2, 64, 32).astype(np.float32))
    step = jax.jit(lambda x: jnp.sum(
        pk.flash_attention(x, x, x, causal=False, block_q=32, block_k=32)))
    acct = MFUAccountant(num_devices=1, peak_flops=1e12)
    flops = acct.maybe_trace(step, (q,))
    assert flops is not None
    assert flops >= 4 * 1 * 2 * 64 * 64 * 32  # the flash_fwd model alone


def test_cost_rows_jaxpr_table_shows_kernels():
    rows, totals = jaxpr_audit.cost_rows(
        lambda x: pk.flash_attention(x, x, x, causal=False,
                                     block_q=32, block_k=32),
        jnp.zeros((1, 1, 64, 32), jnp.float32))
    assert any(r["primitive"] == "pallas::flash_fwd" for r in rows)
    legacy_rows, legacy_totals = jaxpr_audit.cost_rows(
        lambda x: pk.flash_attention(x, x, x, causal=False,
                                     block_q=32, block_k=32),
        jnp.zeros((1, 1, 64, 32), jnp.float32), attribute_kernels=False)
    assert totals["flops"] > legacy_totals["flops"]


@functools.lru_cache(maxsize=None)
def _kernel_family_calls():
    """One traceable call a kernel family, with its operands: what a
    roofline table prices. Built once for the seven cases below."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 2, 128, 32).astype(np.float32))
    slab = jnp.asarray(rng.randn(8, 4096).astype(np.float32))
    spec8 = comm.CompressionSpec("int8", chunk=256)
    spec2 = comm.CompressionSpec("twobit", threshold=0.5, chunk=256)
    shapes = {"p0": (256, 64), "p1": (64,), "p2": (64, 32)}
    params = {n: jnp.asarray(rng.randn(*sh).astype(np.float32))
              for n, sh in shapes.items()}
    adam = opt_mod.Adam(lr=1e-3, fused=True)
    x_mm = jnp.asarray(rng.randn(64, 128).astype(np.float32))
    w_mm = jnp.asarray(rng.randn(64, 128).astype(np.float32))
    return {
        "flash_attention_fwd": (
            lambda x: pk.flash_attention(x, x, x, causal=True), (q,),
            {"pallas::flash_fwd"}),
        "flash_attention_fwd_bwd": (
            lambda x: jax.grad(lambda y: jnp.sum(
                pk.flash_attention(y, y, y, causal=True)))(x), (q,),
            {"pallas::flash_fwd", "pallas::flash_bwd_dq",
             "pallas::flash_bwd_dkv"}),
        "quant_int8": (
            lambda r: pk.fused_quantize(spec8, r, want_dequant=True)[0]["q"],
            (slab,), {"pallas::quant_int8"}),
        "quant_twobit": (
            lambda r: pk.fused_quantize(spec2, r, want_dequant=True)[0]["q"],
            (slab,), {"pallas::quant_twobit"}),
        # the payload is an operand, so the row prices the dequant-sum
        # kernel alone
        "dequant_sum_int8": (
            lambda p: pk.fused_dequant_sum(spec8, p),
            (jax.jit(lambda r: pk.fused_quantize(spec8, r)[0])(slab),),
            {"pallas::dequant_sum_int8"}),
        "fused_adam": (
            lambda p, g, st: pk.fused_adam_apply(
                adam, p, g, st, jnp.float32(1e-3))[0]["p0"],
            (params, params, adam.init_state_tree(params)),
            {"pallas::fused_adam"}),
        "int8_matmul": (
            lambda a, w: pk.int8_matmul(a, w), (x_mm, w_mm),
            {"pallas::int8_matmul"}),
    }


@pytest.mark.parametrize("family", [
    "flash_attention_fwd", "flash_attention_fwd_bwd", "quant_int8",
    "quant_twobit", "dequant_sum_int8", "fused_adam", "int8_matmul"])
def test_cost_rows_price_every_kernel_family(family):
    """A roofline row a kernel family needs the registry to price it: the
    call's jaxpr shows the family's kernels as ``pallas::<name>`` rows,
    each with FLOP and bytes above zero (a kernel without a cost model
    would be invisible to the MFU accountant)."""
    fn, operands, want = _kernel_family_calls()[family]
    rows, _ = jaxpr_audit.cost_rows(fn, *operands)
    krows = [r for r in rows if r["primitive"].startswith("pallas::")]
    assert want <= {r["primitive"] for r in krows}, krows
    for r in krows:
        assert r["flops"] > 0 and r["bytes"] > 0, r


# -- end-to-end: the armed epoch with every kernel on --------------------------

def test_fit_with_kernels_convergence_and_zero_recompile():
    """ACCEPTANCE: compression='int8' + comm_kernels + fused Adam reach
    fp32-parity accuracy, and a RecompileTracker-armed epoch compiles
    nothing after epoch 0 (the kernel paths perturb neither donation nor
    the program signature)."""
    X, y = _blobs(160)

    def train(**kw):
        np.random.seed(0)
        mx.random.seed(0)
        model = mx.FeedForward(_mlp(), ctx=_ctx8(), num_epoch=4,
                               optimizer="adam", learning_rate=0.01,
                               initializer=mx.init.Xavier())
        model.fit(X, y, batch_size=32, **kw)
        return (model.predict(X, batch_size=32).argmax(axis=1) == y).mean()

    acc_fp32 = train()
    tracker = cm.RecompileTracker(raise_on_recompile=True)

    def arm_after_first(epoch, *_):
        if epoch == 0:
            tracker.arm()

    np.random.seed(0)
    mx.random.seed(0)
    model = mx.FeedForward(_mlp(), ctx=_ctx8(), num_epoch=4,
                           optimizer="adam", learning_rate=0.01,
                           initializer=mx.init.Xavier(), fused=True)
    try:
        model.fit(X, y, batch_size=32, compression="int8",
                  comm_kernels=True, epoch_end_callback=arm_after_first)
    finally:
        tracker.disarm()
    assert tracker.recompiles == []
    acc_k = (model.predict(X, batch_size=32).argmax(axis=1) == y).mean()
    assert acc_fp32 > 0.9
    assert abs(acc_k - acc_fp32) < 0.08, (acc_fp32, acc_k)


def test_precompile_with_comm_kernels_then_fit_no_compiles():
    X, y = _blobs(120)
    model = mx.FeedForward(_mlp(hidden=64), ctx=_ctx8(), num_epoch=2,
                           optimizer="adam", learning_rate=0.01,
                           fused=True)
    out = model.precompile(data_shapes={"data": (40, 10)},
                           label_shapes={"softmax_label": (40,)},
                           compression="int8", comm_kernels=True)
    assert out["programs"] == 1
    with cm.RecompileTracker(raise_on_recompile=True):
        model.fit(X, y, batch_size=40, compression="int8",
                  comm_kernels=True)
