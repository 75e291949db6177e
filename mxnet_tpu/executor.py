"""Executor: binds a Symbol to devices and buffers, compiles it with XLA.

Reference counterpart: src/symbol/graph_executor.cc (GraphExecutor) —
which plans memory (inplace rewrite, shared-storage coloring), creates cached
engine ops, and pushes them in topo order on every Forward/Backward. Here all
of that collapses into ``jax.jit``:

  - graph → function     : the Symbol is walked once into a pure function;
                           tracing it yields the jaxpr (≙ StaticGraph).
  - MakeBackwardPass      : ``jax.vjp`` inside a jitted gradient function
                           (reference: static_graph.cc:192-294).
  - memory planner        : XLA buffer assignment + donation
                           (reference: graph_memory_allocator.h).
  - cached engine ops     : the compiled executable, cached by shapes.
  - Forward/Backward push : one async dispatch of a single fused program.

``forward(is_train=True)`` on an executor with bound gradients runs a jitted
program that also emits the VJP residuals (``jax.vjp``'s closure is a
flattenable pytree, so its leaves ride out of the compiled program);
``backward()`` is then a pure backward program over those residuals —
matching the reference contract where Forward/Backward each run their half
of the graph exactly once (graph_executor.cc:616-643). If residual capture
is unavailable on a backend, backward falls back to a fused
forward+backward program (one extra forward).

``debug_str()`` exposes the compiled HLO and per-executable memory stats,
keeping the reference's memory-plan introspection story
(graph_executor.cc:584-614, example/memcost).
"""

from __future__ import annotations

import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import random as _random
from .base import MXNetError
from .context import Context, current_context
from .ndarray import NDArray, zeros
from .ops.registry import REMAT_KEEP
from .utils import compile as compile_mod

__all__ = ["Executor", "simple_bind"]


def _fusion_plan(symbol):
    """Graph-level operator fusion (reference analogue: the graph rewrite
    passes GraphExecutor runs before memory planning, graph_executor.cc).

    Two patterns (ops/nn.py kernels):
      - BatchNorm -> Activation(relu)            => `_bn_act_train(relu=True)`
      - BatchNorm -> _Plus(bn, z) -> Activation(relu)
                                                 => `_bn_add_relu_train`
        (the ResNet bottleneck tail: BN + shortcut add + relu)
    In both, the fused VJP recomputes the relu mask from already-live
    residuals so the intermediate activations are never materialized — on a
    bandwidth-bound ResNet step ~10+ GB/step of HBM traffic.

    Returns (fused_bn, passthrough, skip_bn, fused_add):
      fused_bn    : BN node ids to run with fwd_fused_relu
      passthrough : Activation node ids that become identity
      skip_bn     : BN node ids deferred into a fused add (not executed)
      fused_add   : add node id -> (bn_node, z_operand_index)
    Disabled via MXNET_TPU_FUSE=0.
    """
    from .base import env_int

    if not env_int("MXNET_TPU_FUSE", 1):
        return frozenset(), frozenset(), frozenset(), {}
    nodes = symbol._topo()
    consumers: dict = {}
    for node in nodes:
        if node.is_variable:
            continue
        for s, k in node.inputs:
            consumers.setdefault((id(s), k), []).append(node)
    head_ids = {(id(n), i) for n, i in symbol._heads}

    def _sole_private_output(node):
        return len(consumers.get((id(node), 0), [])) == 1 and \
            (id(node), 0) not in head_ids

    fused_bn, passthrough = set(), set()
    skip_bn, fused_add = set(), {}
    for node in nodes:
        if node.is_variable or node.op.name != "Activation" \
                or node.op.act_type != "relu":
            continue
        src, k = node.inputs[0]
        if k != 0 or src.is_variable:
            continue
        if src.op.name == "BatchNorm":
            if _sole_private_output(src):
                fused_bn.add(id(src))
                passthrough.add(id(node))
        elif src.op.name == "_Plus" and _sole_private_output(src):
            add_node = src
            for z_idx in (1, 0):
                bn, bn_k = add_node.inputs[1 - z_idx]
                if bn_k == 0 and not bn.is_variable \
                        and bn.op.name == "BatchNorm" \
                        and _sole_private_output(bn):
                    skip_bn.add(id(bn))
                    fused_add[id(add_node)] = (bn, z_idx)
                    passthrough.add(id(node))
                    break
    return frozenset(fused_bn), frozenset(passthrough), frozenset(skip_bn), \
        fused_add


def _remat_segments(nodes):
    """Partition the topo order into rematerialization segments.

    Every compute node that is a boundary CLOSES a segment (the node is
    the segment's last member). Each closed segment executes under
    ``jax.checkpoint``: its interior activations are recomputed in the
    backward pass instead of being saved, trading MXU FLOPs for HBM
    traffic and footprint. Two things make a node a boundary:

    - the Symbol says so: the node's operator has ``closes_remat_segment``
      (``RematBoundary``, an identity a model builder puts where a segment
      should end: ``models.laguna`` after every decoder layer, where a
      layer's interior is about 1 GB at 8,192 positions);
    - ``MXNET_TPU_REMAT``, a regex over node names, for a Symbol that
      carries no marker. For the ResNet zoo the unit-output relus are the
      natural boundaries: ``MXNET_TPU_REMAT='unit\\d+_out$'`` saves only
      the per-unit residual streams (doc/performance.md roofline:
      activations crossing HBM dominate the step).

    The trailing run after the last boundary (head: pool/fc/loss) stays
    inline.

    Returns None when there is no boundary at all, else a list of
    ``('inline', topo_idx, node) | ('blk', [(topo_idx, node), ...])``
    segments; each block's external inputs and exports are resolved by
    _build_graph_fn. Variables never join blocks — their env seeds are
    dict lookups, and keeping them out makes every block a pure function
    of real arrays.
    """
    import re

    from .base import env_str

    pat = env_str("MXNET_TPU_REMAT", "")
    rx = re.compile(pat) if pat else None

    def closes(node):
        return getattr(node.op, "closes_remat_segment", False) \
            or (rx is not None and rx.search(node.name))

    if rx is None and not any(closes(n) for n in nodes if not n.is_variable):
        return None

    runs = []  # ('inline', idx, node) | ('blk', [(idx, node), ...])
    cur = []
    for i, node in enumerate(nodes):
        if node.is_variable:
            runs.append(("inline", i, node))
            continue
        cur.append((i, node))
        if closes(node):
            runs.append(("blk", cur))
            cur = []
    for i, node in cur:  # tail after the last boundary: head ops, inline
        runs.append(("inline", i, node))

    return runs


def _build_graph_fn(symbol, is_train: bool, stored=None, stored_dtype=None):
    """Compile the symbol DAG into a pure function of (args, aux, rng).

    ``stored`` maps a variable's name to the axes (major to minor) in
    which its value is handed over permuted (``FeedForward``'s stored
    order: the order the consuming operator reads it in). Each consumer
    takes it back to the declared order as its inputs are read, inside its
    recomputation block and right before the operator's own transposition,
    so the two cancel at trace level and the program reads the leaf where
    it lies."""
    nodes = symbol._topo()
    fused_bn, passthrough, skip_bn, fused_add = _fusion_plan(symbol)
    declared = {(id(n), 0): tuple(int(a) for a in np.argsort(stored[n.name]))
                for n in nodes if n.is_variable and n.name in (stored or {})}

    def read(env, ref):
        value = env[ref]
        if ref not in declared:
            return value
        value = value.transpose(declared[ref])
        return value if stored_dtype is None else value.astype(stored_dtype)

    def node_aux_names(node):
        if id(node) in fused_add:
            bn = fused_add[id(node)][0]
            return [f"{bn.name}_{a}" for a in bn.op.list_auxiliary_states()]
        if node.is_variable or id(node) in skip_bn or id(node) in passthrough:
            return []
        return [f"{node.name}_{a}" for a in node.op.list_auxiliary_states()]

    def node_input_refs(node):
        """The env refs exec_node will read for this node (fusion-aware)."""
        if node.is_variable or id(node) in skip_bn:
            return []
        if id(node) in passthrough:
            src, k = node.inputs[0]
            return [(id(src), k)]
        if id(node) in fused_add:
            bn, z_idx = fused_add[id(node)]
            z_src, z_k = node.inputs[z_idx]
            return [(id(s), k) for s, k in bn.inputs] + [(id(z_src), z_k)]
        return [(id(s), k) for s, k in node.inputs]

    def exec_node(i, node, env, aux_values, new_aux, rng, mask=None):
        """Run one compute node: reads env/aux_values, writes env/new_aux.
        Input refs always come from node_input_refs — the single
        fusion-aware source of truth the remat block resolution also uses,
        so block externals can never disagree with what runs here.
        ``mask`` is the optional (batch,) loss validity mask (PadPolicy):
        loss heads route through fwd_masked so padded rows inject no
        gradient.

        Every op emits under ``jax.named_scope(<layer>/<op>)`` so XLA op
        metadata names its source layer — the provenance the device-time
        profiler (telemetry/profiling.py) joins measured trace events back
        through. Scopes are trace-time metadata only: the jaxpr, the
        compiled program's cache keys, and the zero-recompile invariant
        are untouched, and backward ops inherit the scope through jax's
        transpose machinery."""
        if id(node) in skip_bn:  # executes inside its fused add below
            return
        if id(node) in passthrough:  # relu folded into the producer
            env[(id(node), 0)] = env[node_input_refs(node)[0]]
            return
        with jax.named_scope(f"{node.name}/{node.op.name}"):
            _exec_node_scoped(i, node, env, aux_values, new_aux, rng, mask)

    def _exec_node_scoped(i, node, env, aux_values, new_aux, rng, mask):
        if id(node) in fused_add:
            # node_input_refs ordering contract: bn.inputs..., then z
            refs = node_input_refs(node)
            bn = fused_add[id(node)][0]
            bn_ins = [read(env, r) for r in refs[:-1]]
            z = read(env, refs[-1])
            aux_names = node_aux_names(node)
            aux = [aux_values[a] for a in aux_names]
            outs, updated = bn.op.fwd_fused_add_relu(
                bn_ins + [z], aux, is_train, None)
            env[(id(node), 0)] = outs[0]
            for a_name, a_val in zip(aux_names, updated):
                new_aux[a_name] = a_val
            return
        ins = [read(env, r) for r in node_input_refs(node)]
        aux_names = node_aux_names(node)
        aux = [aux_values[a] for a in aux_names]
        key = jax.random.fold_in(rng, i) if node.op.need_rng else None
        if id(node) in fused_bn:
            outs, updated = node.op.fwd_fused_relu(ins, aux, is_train, key)
        elif mask is not None and node.op.is_loss:
            outs, updated = node.op.fwd_masked(ins, aux, is_train, key, mask)
        else:
            outs, updated = node.op.fwd(ins, aux, is_train, key)
        for k, o in enumerate(outs):
            env[(id(node), k)] = o
        for a_name, a_val in zip(aux_names, updated):
            new_aux[a_name] = a_val

    segments = _remat_segments(nodes)

    if segments is None:
        def fn(arg_values: dict, aux_values: dict, rng, mask=None):
            env = {}
            new_aux = dict(aux_values)
            for i, node in enumerate(nodes):
                if node.is_variable:
                    env[(id(node), 0)] = arg_values[node.name]
                    continue
                exec_node(i, node, env, aux_values, new_aux, rng, mask)
            outputs = tuple(env[(id(n), i)] for n, i in symbol._heads)
            return outputs, new_aux

        return fn

    # -- remat path: resolve each block's external inputs and exports ------
    head_refs = {(id(n), i) for n, i in symbol._heads}
    blocks = []  # ('inline', idx, node) | ['blk', members, exts, outs, auxs]
    for seg in segments:
        if seg[0] == "inline":
            blocks.append(seg)
            continue
        members = seg[1]
        member_ids = {id(n) for _, n in members}
        exts, seen = [], set()
        for _, node in members:
            for ref in node_input_refs(node):
                if ref[0] not in member_ids and ref not in seen:
                    seen.add(ref)
                    exts.append(ref)
        aux_names = []
        for _, node in members:
            aux_names.extend(node_aux_names(node))
        blocks.append(["blk", members, exts, [], aux_names])

    # export = block-produced ref consumed by a LATER block/inline node or
    # a graph head. Walk again with per-node producer tracking.
    producer = {}  # node id -> index into blocks (only for blk segments)
    for bi, seg in enumerate(blocks):
        if seg[0] == "inline":
            continue
        for _, node in seg[1]:
            # a node may emit several outputs; record by node id, the
            # consumer side supplies the out_idx
            producer[id(node)] = bi

    def note_consumption(ref, consumer_bi):
        node_id, _ = ref
        pbi = producer.get(node_id)
        if pbi is not None and pbi != consumer_bi:
            out_list = blocks[pbi][3]
            if ref not in out_list:
                out_list.append(ref)

    for bi, seg in enumerate(blocks):
        if seg[0] == "inline":
            for ref in node_input_refs(seg[2]):
                note_consumption(ref, bi)
        else:
            for _, node in seg[1]:
                for ref in node_input_refs(node):
                    note_consumption(ref, bi)
    for ref in head_refs:
        note_consumption(ref, -1)

    def make_block_fn(members, exts, out_refs, aux_names):
        def block_fn(ext_vals, aux_vals, rng, mask):
            env = dict(zip(exts, ext_vals))
            aux_in = dict(zip(aux_names, aux_vals))
            new_aux = {}
            for i, node in members:
                exec_node(i, node, env, aux_in, new_aux, rng, mask)
            return (tuple(env[r] for r in out_refs),
                    tuple(new_aux.get(a, aux_in[a]) for a in aux_names))

        # what an operator names REMAT_KEEP is kept across the boundary
        # and not recomputed (ops/pallas/flash_attention.py keeps its
        # output and row statistics: recomputing them is the forward
        # kernel again); everything else is, as without a policy
        return jax.checkpoint(
            block_fn,
            policy=jax.checkpoint_policies.save_only_these_names(REMAT_KEEP))

    compiled_blocks = []
    for seg in blocks:
        if seg[0] == "inline":
            compiled_blocks.append(seg)
        else:
            _, members, exts, out_refs, aux_names = seg
            compiled_blocks.append(
                ("blk", make_block_fn(members, exts, out_refs, aux_names),
                 exts, out_refs, aux_names))

    def fn(arg_values: dict, aux_values: dict, rng, mask=None):
        env = {}
        new_aux = dict(aux_values)
        for seg in compiled_blocks:
            if seg[0] == "inline":
                _, i, node = seg
                if node.is_variable:
                    env[(id(node), 0)] = arg_values[node.name]
                else:
                    exec_node(i, node, env, aux_values, new_aux, rng, mask)
                continue
            _, block_fn, exts, out_refs, aux_names = seg
            outs, updated = block_fn(
                tuple(env[r] for r in exts),
                tuple(aux_values[a] for a in aux_names), rng, mask)
            env.update(zip(out_refs, outs))
            new_aux.update(zip(aux_names, updated))
        outputs = tuple(env[(id(n), i)] for n, i in symbol._heads)
        return outputs, new_aux

    return fn


def _normalize(names, values, what):
    if values is None:
        return {}
    if isinstance(values, dict):
        return dict(values)
    values = list(values)
    if len(values) != len(names):
        raise MXNetError(f"{what}: expected {len(names)} entries, got {len(values)}")
    return dict(zip(names, values))


class Executor:
    """A bound computation (reference: include/mxnet/symbolic.h Executor)."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None):
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else current_context()
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        self.arg_dict = _normalize(arg_names, args, "args")
        missing = [n for n in arg_names if n not in self.arg_dict]
        if missing:
            raise MXNetError(f"bind: missing arguments {missing}")
        self.grad_dict = _normalize(arg_names, args_grad, "args_grad")
        self.aux_dict = _normalize(aux_names, aux_states, "aux_states")
        if set(aux_names) - set(self.aux_dict):
            raise MXNetError(
                f"bind: missing aux states {sorted(set(aux_names) - set(self.aux_dict))}"
            )
        if isinstance(grad_req, str):
            self.grad_req = {n: grad_req for n in arg_names}
        else:
            self.grad_req = dict(_normalize(arg_names, grad_req, "grad_req"))
        for n in arg_names:
            self.grad_req.setdefault(n, "null")

        # pre-bind graph verification (mxlint Pass 2; reference:
        # StaticGraph::InferShape runs before GraphExecutor binds): full
        # shape+dtype inference and structural checks against the actual
        # bound buffers, so conflicts fail HERE with the op named instead
        # of deep inside XLA tracing. MXNET_TPU_VERIFY=0 disables.
        from .base import env_bool

        if env_bool("MXNET_TPU_VERIFY", True):
            symbol.verify(
                arg_shapes={n: tuple(a.shape)
                            for n, a in self.arg_dict.items()},
                arg_dtypes={n: a.dtype for n, a in self.arg_dict.items()})

        self._fwd_fns = {}  # is_train -> tracked jitted fn
        self._graph_fp = None  # lazy graph fingerprint (program labels)
        self._bwd_fn = None
        self._outputs: list[NDArray] | None = None
        self._last = None  # (arg_vals, aux_vals, rng) of last is_train fwd
        self._needs_rng = any(
            (not n.is_variable) and n.op.need_rng for n in symbol._topo()
        )
        # residual-capturing forward (see module docstring): jitted fn,
        # treedef cell, jitted backward-apply, and the live residual leaves
        self._fwd_res_fn = None
        self._res_cell: dict = {}
        self._bwd_apply_fn = None
        self._res_leaves = None
        self._res_ok = True  # flips off after a failed capture attempt

    def _label(self, kind: str) -> str:
        """Program-registry label: graph fingerprint + program kind. The
        fingerprint folds in the fusion/remat flags, so 'same symbol,
        different rewrite config' shows up as distinct programs."""
        if self._graph_fp is None:
            self._graph_fp = compile_mod.graph_fingerprint(self._symbol)
        return f"executor:{self._graph_fp}:{kind}"

    # -- public surface -------------------------------------------------------
    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._symbol.list_arguments()]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._symbol.list_arguments()]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._symbol.list_auxiliary_states()]

    @property
    def outputs(self):
        if self._outputs is None:
            raise MXNetError("call forward() before reading outputs")
        return self._outputs

    def forward(self, is_train=False, **kwargs):
        from . import telemetry

        telemetry.counter("executor_forward_total")
        with telemetry.phase("executor_forward"):
            return self._forward_impl(is_train, **kwargs)

    def _forward_impl(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError(f"forward: unknown argument {k!r}")
            src = v if isinstance(v, NDArray) else NDArray(v)
            src.copyto(self.arg_dict[k])
        arg_vals = {n: a._data for n, a in self.arg_dict.items()}
        aux_vals = {n: a._data for n, a in self.aux_dict.items()}
        rng = _random.next_key() if self._needs_rng else jnp.zeros((2,), jnp.uint32)

        is_train = bool(is_train)
        diff_names = self._diff_names()
        if is_train and diff_names and self._res_ok:
            try:
                outs, new_aux = self._forward_with_residuals(
                    arg_vals, aux_vals, rng, diff_names)
            except Exception:  # pragma: no cover - backend-dependent
                self._res_ok = False
                self._res_leaves = None
                outs = None
        else:
            outs = None
        if outs is None:
            outs, new_aux = self._get_fwd_fn(is_train)(arg_vals, aux_vals,
                                                       rng)

        if is_train:
            self._last = (arg_vals, aux_vals, rng)
            for n, a in self.aux_dict.items():
                a._set_data(new_aux[n])
        if self._outputs is None:
            self._outputs = [NDArray(o) for o in outs]
        else:
            for holder, o in zip(self._outputs, outs):
                holder._data = o  # outputs are framework-owned; bypass writable
        return self._outputs

    def _diff_names(self):
        return sorted(n for n, r in self.grad_req.items() if r != "null")

    def _get_fwd_fn(self, is_train):
        if is_train not in self._fwd_fns:
            fn = _build_graph_fn(self._symbol, is_train)
            kind = "fwd_train" if is_train else "fwd_eval"
            self._fwd_fns[is_train] = compile_mod.tracked_jit(
                fn, label=self._label(kind))
        return self._fwd_fns[is_train]

    def _get_fwd_res_fn(self):
        if self._fwd_res_fn is None:
            fwd = _build_graph_fn(self._symbol, True)
            cell = self._res_cell

            def fwd_res(diff_args, other_args, aux, rng):
                def inner(d):
                    outs, new_aux = fwd({**d, **other_args}, aux, rng)
                    return tuple(outs), new_aux

                outs, vjp_fn, new_aux = jax.vjp(inner, diff_args,
                                                has_aux=True)
                leaves, treedef = jax.tree_util.tree_flatten(vjp_fn)
                cell["treedef"] = treedef
                return outs, new_aux, leaves

            self._fwd_res_fn = compile_mod.tracked_jit(
                fwd_res, label=self._label("fwd_train_res"))
        return self._fwd_res_fn

    def _forward_with_residuals(self, arg_vals, aux_vals, rng, diff_names):
        """Run forward AND capture the VJP residuals in one compiled program.

        jax.vjp's returned closure is a registered pytree whose leaves are
        the residual arrays, so a jitted function can emit them; the treedef
        (recorded at trace time) reconstructs the closure inside the jitted
        backward. This is what makes Forward/Backward each run once, like
        the reference's split executor."""
        self._get_fwd_res_fn()
        diff_args = {n: arg_vals[n] for n in diff_names}
        other = {n: v for n, v in arg_vals.items() if n not in diff_args}
        outs, new_aux, leaves = self._fwd_res_fn(diff_args, other, aux_vals,
                                                 rng)
        self._res_leaves = leaves
        return outs, new_aux

    def backward(self, out_grads=None):
        """Compute gradients into the bound grad arrays (reference:
        GraphExecutor::Backward). Seeds ones for missing head gradients; loss
        heads ignore the seed by construction (see ops/loss.py)."""
        if self._last is None:
            raise MXNetError("backward() requires a prior forward(is_train=True)")
        from . import telemetry

        telemetry.counter("executor_backward_total")
        with telemetry.phase("executor_backward"):
            return self._backward_impl(out_grads)

    def _backward_impl(self, out_grads=None):
        arg_vals, aux_vals, rng = self._last
        diff_names = self._diff_names()
        if not diff_names:
            return
        if out_grads is None:
            cots = tuple(jnp.ones_like(o._data) for o in self.outputs)
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            cots = tuple(g._data for g in out_grads)

        if self._res_leaves is not None:
            if self._bwd_apply_fn is None:
                cell = self._res_cell

                def bwd_apply(leaves, cots):
                    vjp_fn = jax.tree_util.tree_unflatten(cell["treedef"],
                                                          leaves)
                    (grads,) = vjp_fn(cots)
                    return grads

                self._bwd_apply_fn = compile_mod.tracked_jit(
                    bwd_apply, label=self._label("bwd_apply"))
            leaves, self._res_leaves = self._res_leaves, None
            # drop the residual references as soon as backward consumes them
            # so activation memory frees before the caller's optimizer
            # update; a second backward() without a new forward falls
            # through to the fused-recompute path below
            try:
                grads = self._bwd_apply_fn(leaves, cots)
            except Exception:  # pragma: no cover - backend-dependent
                # e.g. residual leaves whose treedef no longer matches, or
                # non-array leaves a backend rejects: disable residual
                # capture and recompute via the fused path (self._last
                # still holds the forward inputs)
                logging.warning(
                    "residual-path backward failed; falling back to fused "
                    "forward+backward recompute for this executor "
                    "(slower: forward re-runs every backward)",
                    exc_info=True)
                self._res_ok = False
                self._bwd_apply_fn = None
            else:
                self._write_grads(diff_names, grads)
                return

        if self._bwd_fn is None:
            fwd = _build_graph_fn(self._symbol, True)

            def bwd(diff_args, other_args, aux, rng, cotangents):
                def f(d):
                    outs, _ = fwd({**d, **other_args}, aux, rng)
                    return outs

                _, vjp_fn = jax.vjp(f, diff_args)
                (grads,) = vjp_fn(cotangents)
                return grads

            self._bwd_fn = compile_mod.tracked_jit(
                bwd, label=self._label("bwd_fused"))

        diff_args = {n: arg_vals[n] for n in diff_names}
        other = {n: v for n, v in arg_vals.items() if n not in diff_args}
        grads = self._bwd_fn(diff_args, other, aux_vals, rng, cots)
        self._write_grads(diff_names, grads)

    def _write_grads(self, diff_names, grads):
        for n in diff_names:
            req = self.grad_req[n]
            holder = self.grad_dict.get(n)
            if holder is None:
                continue
            g = grads[n].astype(holder.dtype)
            if req == "add":
                holder._set_data(holder._data + g)
            else:  # write
                holder._set_data(g)

    def precompile(self, is_train=False):
        """AOT warmup: lower + compile the forward program this executor
        would dispatch, before the first ``forward()`` call pays the stall
        (``.lower().compile()`` via the compile registry — see
        doc/developer-guide/compile_cache.md). Compiles the SAME program
        ``forward(is_train=...)`` will run: with bound gradients the
        residual-capturing train forward, else the plain forward. Returns
        the wall seconds spent compiling (0.0 when already warm)."""
        arg_structs = {n: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)
                       for n, a in self.arg_dict.items()}
        aux_structs = {n: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)
                       for n, a in self.aux_dict.items()}
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        is_train = bool(is_train)
        diff_names = self._diff_names()
        t0 = time.perf_counter()
        if is_train and diff_names and self._res_ok:
            diff = {n: arg_structs[n] for n in diff_names}
            other = {n: v for n, v in arg_structs.items() if n not in diff}
            self._get_fwd_res_fn().precompile(diff, other, aux_structs, rng)
        else:
            self._get_fwd_fn(is_train).precompile(arg_structs, aux_structs,
                                                  rng)
        return time.perf_counter() - t0

    def copy_params_from(self, arg_params, aux_params=None):
        """Copy parameter dicts into the bound arrays (reference:
        Executor::CopyParamsFrom used by FeedForward)."""
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                arr.copyto(self.arg_dict[name])
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    arr.copyto(self.aux_dict[name])

    def debug_str(self) -> str:
        """Compiled-program introspection (reference: GraphExecutor::Print —
        'Total N MB allocated'). The memory block is read from the
        registered memory plan whenever one exists (AOT warmup and any
        prior ``debug_str`` register it — ISSUE 9), so printing it costs a
        dict lookup; only a never-compiled executor pays the historical
        re-lower+compile path, which then registers the plan for next
        time."""
        lines = [self._symbol.debug_str()]
        reg = compile_mod.registry()
        # candidate labels in the order the compiled-fallback path would
        # pick programs: the live forward fns, then the residual-capture
        # train program, then the never-materialized kinds
        candidates = [fn.label for key in (True, False)
                      if (fn := self._fwd_fns.get(key)) is not None]
        candidates += [self._label("fwd_train_res"),
                       self._label("fwd_train"), self._label("fwd_eval")]
        # labels key on the graph fingerprint, not shapes: another
        # executor of the SAME symbol bound at different shapes shares the
        # label, so only trust a plan whose argument bytes are within 10%
        # of THIS executor's bound buffers (slack: XLA prunes unused args
        # like the rng key, and TPU layouts pad; different batch shapes
        # diverge far more than 10% — and when they don't, the totals are
        # near-identical anyway). A mismatch falls back to one compile.
        expected_args = 8 + sum(
            int(np.prod(a.shape, dtype=np.int64)) * a.dtype.itemsize
            for d in (self.arg_dict, self.aux_dict) for a in d.values())
        plan = None
        for label in candidates:
            plan = reg.memory_plan_for(label)
            if plan is not None and not (
                    0.9 * expected_args <= plan.get("argument_bytes", 0)
                    <= 1.1 * expected_args):
                plan = None
            if plan is not None:
                break
        if plan is None:
            plan = self._compile_memory_plan(reg)
        if plan is not None:
            lines.append(f"Total {plan['total_bytes'] / (1 << 20):.4f} MB "
                         "allocated")
            lines.append(
                f"Temp {plan['temp_bytes'] / (1 << 20):.4f} MB, "
                f"args {plan['argument_bytes'] / (1 << 20):.4f} MB")
        else:
            lines.append("Total memory: unavailable on this backend")
        return "\n".join(lines)

    def _compile_memory_plan(self, reg):
        """Fallback for a program that never AOT-compiled: lower+compile
        the forward this executor would dispatch, extract its plan, and
        register it so the next debug_str (and the telemetry exports) read
        it for free."""
        fn = self._fwd_fns.get(True) or self._fwd_fns.get(False)
        arg_vals = {n: a._data for n, a in self.arg_dict.items()}
        aux_vals = {n: a._data for n, a in self.aux_dict.items()}
        rng = jnp.zeros((2,), jnp.uint32)
        compiled = label = None
        try:
            if fn is None and self._fwd_res_fn is None:
                # never dispatched: build (don't run) the eval forward so
                # bind+debug_str still reports a memory plan
                fn = self._get_fwd_fn(False)
            if fn is not None:
                compiled, label = fn.lower(arg_vals, aux_vals,
                                           rng).compile(), fn.label
            elif self._fwd_res_fn is not None:
                # train forwards ran through the residual-capture program
                diff = {n: arg_vals[n] for n in self._diff_names()}
                other = {n: v for n, v in arg_vals.items() if n not in diff}
                compiled = self._fwd_res_fn.lower(diff, other, aux_vals,
                                                  rng).compile()
                label = self._fwd_res_fn.label
        except Exception:  # backend-dependent lowering failure
            return None
        if compiled is None:
            return None
        plan = compile_mod.memory_plan_from_compiled(compiled)
        if plan is not None and label is not None:
            reg.record_memory_plan(label, plan)
        return plan


def simple_bind(symbol, ctx, grad_req="write", **input_shapes) -> Executor:
    """Allocate all buffers from inferred shapes and bind (reference:
    symbol.py simple_bind → MXExecutorBind)."""
    arg_shapes, _, aux_shapes = symbol.infer_shape(**input_shapes)
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    args = {n: zeros(s, ctx) for n, s in zip(arg_names, arg_shapes)}
    if isinstance(grad_req, str):
        reqs = {n: grad_req for n in arg_names}
    elif isinstance(grad_req, dict):
        reqs = {n: grad_req.get(n, "null") for n in arg_names}
    else:
        reqs = dict(zip(arg_names, grad_req))
    grads = {
        n: zeros(s, ctx)
        for n, s in zip(arg_names, arg_shapes)
        if reqs.get(n, "null") != "null"
    }
    aux = {n: zeros(s, ctx) for n, s in zip(aux_names, aux_shapes)}
    return Executor(symbol, ctx, args, grads, reqs, aux)
