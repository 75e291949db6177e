"""mxlint Pass 1: AST-based source lint.

Catches, before anything imports or traces:
  MX101/MX102  version-fragile / deprecated JAX import paths (the class of
               failure that bricked the seed: ``from jax import shard_map``
               took out all 75 test modules at collection time),
  MX201-203    host-sync hazards inside traced code (numpy calls, .item(),
               float()/int() on traced values, Python branches on tracers),
  MX301-302    recompilation risks (unhashable static-arg containers,
               string formatting under trace),
  MX306        un-barriered wall-clock deltas around device dispatch
               (timing the enqueue instead of the execution; telemetry/
               and utils/profiler are the sanctioned timing homes),
  MX308        wire collectives in comm/ without optimization_barrier
               pinning on both sides (XLA commutes the encode/decode
               converts across the collective: fp32 on the wire,
               compression silently lost),
  MX309        implicit host syncs (.asnumpy()/.item()/np.asarray) inside
               a loop that dispatches the train/eval/predict step — each
               pull serializes async dispatch and skews memory accounting
               (intentional per-step syncs carry a disable pragma),
  MX310        world-size/axis-size integer literals captured in closures
               outside the mesh/coordinator providers — a size frozen at
               build time goes stale when the elastic world resizes
               mid-run (derive from the live mesh/kvstore/coordinator),
  MX311        direct fleet actuation (ElasticCoordinator.kill/
               request_world, set_gradient_compression) outside
               resilience/controller.py — actuation must flow through
               the FleetController policy loop and its safety rails,
  MX313        per-leaf Python loops over gradient pytrees inside traced
               functions that materialize per-leaf host stats (float()/
               .item()/numpy per parameter per step) — the pattern the
               in-graph health stats engine (telemetry.health) replaces
               with one fused per-layer reduction + a single pull,
  MX314        raw jax.profiler captures (start_trace/trace) outside
               utils/profiler.py / telemetry/profiling.py, and any
               start_trace without a finally-guarded stop — the profiler
               is process-global, so strays race the framework's bounded
               capture windows and a leaked trace breaks every later one
               (telemetry.profiling.capture() is the sanctioned shape),
  MX315        direct sharded-checkpoint writes (save_sharded /
               _write_manifest) outside utils/checkpoint.py /
               resilience/ckpt_async.py — the checkpoint plane owns
               durability ordering (tmp-dir staging, CRC commit,
               retention GC, writer-thread flush barriers), so strays
               race the async writer and dodge badput pricing
               (ckpt_async.save_now / AsyncCheckpointWriter.submit are
               the sanctioned shapes),
  MX316        hand-rolled run-summary emission (emit("run_summary", ...))
               or direct MXNET_TPU_LEDGER_DIR consultation outside
               telemetry/ledger.py — the cross-run ledger owns the
               RunRecord schema and the atomic CRC'd append, so strays
               produce history the trend/compare gates cannot read
               (telemetry.ledger.record_run / ledger_dir() are the
               sanctioned shapes),
  MX601-602    robustness hazards (bare ``except:``; ``while True`` retry
               loops that swallow exceptions with no backoff/deadline —
               the loop shape that melts a parameter server under a
               partial outage; resilience.retry.RetryPolicy is the
               sanctioned alternative).

Traced-context detection is intentionally heuristic: a function counts as
traced when it is *visibly* wired into JAX tracing — decorated with
jit/vmap/grad/checkpoint (directly or via functools.partial), passed to a
known tracing entry point (jit, shard_map, lax.scan/cond/while_loop/
fori_loop/switch, custom-vjp defvjp, ...), or nested inside such a
function. Closures that escape through variables are not chased; the lint
favors zero false positives on error-severity rules over recall, since the
self-lint gates the tier-1 suite (tools/run_mxlint.py).

This module itself must not import jax (nor the linted files — everything
is AST-level), keeping Pass 1 cheap and side-effect-free. Note the ``-m``
CLI entry still pays the ``mxnet_tpu`` package import (jax is a hard
dependency of the package); only the lint work itself is jax-free.
"""

from __future__ import annotations

import ast
import os

from .rules import Finding, get_rule

__all__ = ["lint_source", "lint_file", "lint_paths", "iter_python_files"]

# import path -> why it is fragile across the supported range
FRAGILE_JAX_IMPORTS = {
    "jax.shard_map":
        "only exists in jax>=0.6 (lives at jax.experimental.shard_map "
        "before that)",
    "jax.experimental.shard_map":
        "removed in jax>=0.7 (promoted to jax.shard_map)",
    "jax.experimental.maps":
        "removed in jax 0.4.31 (xmap retired)",
    "jax.linear_util":
        "removed in jax 0.4.24 (moved to jax.extend.linear_util)",
    "jax.abstract_arrays":
        "removed in jax 0.4.25 (merged into jax.core avals)",
    "jax.experimental.host_callback":
        "removed in jax 0.4.35 (replaced by jax.pure_callback/io_callback)",
}

DEPRECATED_JAX_IMPORTS = {
    "jax.experimental.pjit":
        "pjit is jax.jit since 0.4; the experimental path is slated for "
        "removal",
    "jax.interpreters.xla":
        "progressively gutted since 0.4.x; most symbols have no "
        "replacement at this path",
}

# tracing entry point -> positions of function-valued operands
TRACING_CALLS = {
    "jax.jit": (0,),
    "jax.vmap": (0,),
    "jax.pmap": (0,),
    "jax.grad": (0,),
    "jax.value_and_grad": (0,),
    "jax.checkpoint": (0,),
    "jax.remat": (0,),
    "jax.vjp": (0,),
    "jax.jvp": (0,),
    "jax.linearize": (0,),
    "jax.make_jaxpr": (0,),
    "jax.eval_shape": (0,),
    "jax.custom_vjp": (0,),
    "jax.custom_jvp": (0,),
    "jax.lax.scan": (0,),
    "jax.lax.map": (0,),
    "jax.lax.associative_scan": (0,),
    "jax.lax.fori_loop": (2,),
    "jax.lax.while_loop": (0, 1),
    "jax.lax.cond": (1, 2),
    "jax.lax.switch": (1, 2, 3, 4, 5),
    "jax.shard_map": (0,),
    "jax.experimental.shard_map.shard_map": (0,),
    "mxnet_tpu.compat.shard_map": (0,),
    "compat.shard_map": (0,),
    "jax.experimental.pjit.pjit": (0,),
}

# jit-wrapper factories: creating one of these per call/iteration discards
# the compile cache it carries — the classic recompile bug (MX303)
_JIT_FAMILY = ("jax.jit", "jax.pmap", "jax.experimental.pjit.pjit",
               "mxnet_tpu.utils.compile.tracked_jit", "compile.tracked_jit",
               "compile_mod.tracked_jit")


def _is_jit_family(path):
    if path is None:
        return False
    for key in _JIT_FAMILY:
        if path == key or path.endswith("." + key) or key.endswith("." + path):
            return True
    return False


# functions passed here run on HOST even when called from traced code —
# their bodies are exempt from the traced-code hazard rules
CALLBACK_CALLS = {
    "jax.pure_callback": (0,),
    "jax.io_callback": (0,),
    "jax.debug.callback": (0,),
    "jax.experimental.io_callback": (0,),
}

_HOST_SYNC_ATTRS = ("item", "tolist")
_HOST_CAST_FUNCS = ("float", "int", "bool", "complex")
_SKIP_DIRS = {".git", "__pycache__", ".claude", ".pytest_cache", "node_modules"}


def _mentions_grad(node) -> bool:
    """Does an expression name something gradient-shaped? (MX304 heuristic:
    identifiers/attributes containing 'grad' — zero-FP over recall.)"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and "grad" in sub.id.lower():
            return True
        if isinstance(sub, ast.Attribute) and "grad" in sub.attr.lower():
            return True
    return False


def _in_comm_package(path: str) -> bool:
    """mxnet_tpu/comm is the sanctioned home for raw gradient psums."""
    return "mxnet_tpu/comm" in path.replace(os.sep, "/")


def _dotted(expr, imports):
    """Resolve an expression to a dotted path via the module's import map.
    Returns None when the root name is not an imported module/symbol."""
    parts = []
    node = expr
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    root = imports.get(node.id)
    if root is None:
        return None
    parts.append(root)
    return ".".join(reversed(parts))


def _match_tracing(path):
    """Return function-operand positions when ``path`` names a tracing
    entry point (suffix-tolerant: 'lax.scan' matches 'jax.lax.scan')."""
    if path is None:
        return None
    for key, pos in TRACING_CALLS.items():
        if path == key or path.endswith("." + key) or key.endswith("." + path):
            return pos
    return None


class _ModuleScan(ast.NodeVisitor):
    """One pass over the module: imports, import findings, traced roots."""

    def __init__(self, path):
        self.path = path
        self.imports: dict[str, str] = {}
        self.findings: list[Finding] = []
        self.traced_names: set[str] = set()
        self.traced_lambdas: list[ast.Lambda] = []
        self.host_names: set[str] = set()
        self.host_lambdas: set[int] = set()
        self.defs: list[ast.FunctionDef] = []
        self._loop_depth = 0

    # -- loop tracking (MX303: jit wrapper creation inside a loop) ------------
    def visit_For(self, node):
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    visit_AsyncFor = visit_For

    def visit_While(self, node):
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    # -- imports --------------------------------------------------------------
    def _check_import_path(self, full, node):
        for table, rule_id in ((FRAGILE_JAX_IMPORTS, "MX101"),
                               (DEPRECATED_JAX_IMPORTS, "MX102")):
            for banned, why in table.items():
                if full == banned or full.startswith(banned + "."):
                    self.findings.append(Finding(
                        get_rule(rule_id), f"`{full}`: {why}",
                        path=self.path, line=node.lineno,
                        col=node.col_offset))
                    return

    def visit_Import(self, node):
        for alias in node.names:
            if alias.asname:  # `import a.b as x` binds x to the full path
                self.imports[alias.asname] = alias.name
            else:  # `import a.b.c` binds only the root name `a`
                root = alias.name.split(".")[0]
                self.imports[root] = root
            self._check_import_path(alias.name, node)

    def visit_ImportFrom(self, node):
        mod = ("." * node.level) + (node.module or "")
        for alias in node.names:
            full = f"{mod}.{alias.name}" if mod else alias.name
            self.imports[alias.asname or alias.name] = full.lstrip(".")
            self._check_import_path(full.lstrip("."), node)

    # -- traced-root discovery ------------------------------------------------
    def _mark_fn_operand(self, arg):
        if isinstance(arg, ast.Lambda):
            self.traced_lambdas.append(arg)
        elif isinstance(arg, ast.Name):
            self.traced_names.add(arg.id)
        elif isinstance(arg, ast.Call):
            # functools.partial(fn, ...) / jax.checkpoint(fn) wrapping
            for inner in arg.args:
                self._mark_fn_operand(inner)

    def _mark_host_operand(self, arg):
        if isinstance(arg, ast.Lambda):
            self.host_lambdas.add(id(arg))
        elif isinstance(arg, ast.Name):
            self.host_names.add(arg.id)

    def visit_Call(self, node):
        dotted = _dotted(node.func, self.imports)
        # MX303(a): `jax.jit(fn)(...)` — the wrapper (and its compile
        # cache) dies with the expression; every call re-traces+recompiles
        if isinstance(node.func, ast.Call):
            inner = _dotted(node.func.func, self.imports)
            if _is_jit_family(inner):
                self.findings.append(Finding(
                    get_rule("MX303"),
                    f"`{inner}(fn)(...)` builds a fresh jit wrapper and "
                    "discards it after one call",
                    path=self.path, line=node.lineno, col=node.col_offset))
        # MX304: raw psum over gradient-named values — uncompressed,
        # unbucketed gradient sync outside the comm subsystem. Two shapes:
        # (a) lax.psum(grads/...) directly; (b) the tree_map(lambda g:
        # lax.psum(g, ax), grads) idiom, where the lambda's parameter hides
        # the gradient name but a sibling argument carries it.
        if not _in_comm_package(self.path):
            if dotted is not None and dotted.endswith("psum") and node.args \
                    and _mentions_grad(node.args[0]):
                self.findings.append(Finding(
                    get_rule("MX304"),
                    f"`{dotted}` over a gradient pytree bypasses the comm "
                    "subsystem (fp32, no bucketing, no wire accounting)",
                    path=self.path, line=node.lineno, col=node.col_offset))
            elif dotted is not None and dotted.endswith("tree_map") and \
                    any(_mentions_grad(a) for a in node.args[1:]):
                fn_arg = node.args[0] if node.args else None
                if fn_arg is not None:
                    for sub in ast.walk(fn_arg):
                        if isinstance(sub, ast.Call):
                            inner = _dotted(sub.func, self.imports)
                            if inner is not None and inner.endswith("psum"):
                                self.findings.append(Finding(
                                    get_rule("MX304"),
                                    f"`{inner}` mapped over a gradient "
                                    "pytree bypasses the comm subsystem",
                                    path=self.path, line=sub.lineno,
                                    col=sub.col_offset))
                                break
        # MX303(b): a jit wrapper created inside a loop body is re-created
        # (cache lost) on every iteration
        if _is_jit_family(dotted) and self._loop_depth > 0:
            self.findings.append(Finding(
                get_rule("MX303"),
                f"`{dotted}` called inside a loop: the wrapper's compile "
                "cache is discarded every iteration",
                path=self.path, line=node.lineno, col=node.col_offset))
        for key, positions in CALLBACK_CALLS.items():
            if dotted is not None and (dotted == key
                                       or key.endswith("." + dotted)
                                       or dotted.endswith("." + key)):
                for i in positions:
                    if i < len(node.args):
                        self._mark_host_operand(node.args[i])
        pos = _match_tracing(dotted)
        if pos is None and dotted is not None and \
                dotted.endswith("partial") and any(
                    _match_tracing(_dotted(a, self.imports)) is not None
                    for a in node.args):
            pos = ()  # functools.partial(jax.jit, ...): kwargs still checked
        if pos is not None:
            for i in pos:
                if i < len(node.args):
                    self._mark_fn_operand(node.args[i])
            for kw in node.keywords:
                if kw.arg not in ("static_argnums", "static_argnames"):
                    continue
                if isinstance(kw.value, (ast.List, ast.Set, ast.Dict)):
                    self.findings.append(Finding(
                        get_rule("MX301"),
                        f"`{kw.arg}` given a "
                        f"{type(kw.value).__name__.lower()} literal",
                        path=self.path, line=node.lineno,
                        col=node.col_offset))
                elif isinstance(kw.value, (ast.ListComp, ast.SetComp,
                                           ast.DictComp, ast.GeneratorExp)) \
                        or (isinstance(kw.value, ast.Call)
                            and isinstance(kw.value.func, ast.Name)
                            and kw.value.func.id in ("list", "set", "dict")):
                    # MX303(c): unstable static arg — freshly built /
                    # unhashable value defeats the jit cache key every call
                    self.findings.append(Finding(
                        get_rule("MX303"),
                        f"`{kw.arg}` computed per call "
                        f"({type(kw.value).__name__}): static args are "
                        "jit-cache keys and must be stable hashables",
                        path=self.path, line=node.lineno,
                        col=node.col_offset))
        elif isinstance(node.func, ast.Attribute) and \
                node.func.attr == "defvjp":
            for arg in node.args:  # custom_vjp fwd/bwd pair
                self._mark_fn_operand(arg)
        self.generic_visit(node)

    def visit_FunctionDef(self, node):
        self.defs.append(node)
        for dec in node.decorator_list:
            target = dec
            candidates = [dec]
            if isinstance(dec, ast.Call):
                candidates = [dec.func] + list(dec.args)
            for target in candidates:
                if _match_tracing(_dotted(target, self.imports)) is not None:
                    self.traced_names.add(node.name)
                    break
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef


class _TracedWalk(ast.NodeVisitor):
    """Hazard scan inside one traced root (nested defs included)."""

    def __init__(self, scan: _ModuleScan, params: set[str]):
        self.scan = scan
        self.params = params

    def _flag(self, rule_id, msg, node):
        self.scan.findings.append(Finding(
            get_rule(rule_id), msg, path=self.scan.path,
            line=node.lineno, col=node.col_offset))

    def visit_FunctionDef(self, node):
        if node.name in self.scan.host_names:
            return  # callback body: runs on host, numpy etc. is correct
        self.params.update(a.arg for a in node.args.args
                           if a.arg not in ("self", "cls"))
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        if id(node) in self.scan.host_lambdas:
            return
        self.params.update(a.arg for a in node.args.args)
        self.generic_visit(node)

    def visit_Call(self, node):
        dotted = _dotted(node.func, self.scan.imports)
        if dotted is not None and (dotted == "numpy"
                                   or dotted.startswith("numpy.")):
            self._flag("MX201",
                       f"`{dotted}(...)` runs on host at trace time",
                       node)
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in _HOST_SYNC_ATTRS and not node.args:
            self._flag("MX202",
                       f"`.{node.func.attr}()` blocks on device-to-host "
                       "transfer inside traced code", node)
        if isinstance(node.func, ast.Name) and \
                node.func.id in _HOST_CAST_FUNCS and len(node.args) == 1 \
                and isinstance(node.args[0], ast.Name) \
                and node.args[0].id in self.params:
            self._flag("MX202",
                       f"`{node.func.id}({node.args[0].id})` forces a host "
                       "sync on a traced value", node)
        self.generic_visit(node)

    def _test_touches_param(self, test):
        if isinstance(test, ast.Name):
            return test.id in self.params
        if isinstance(test, ast.Compare):
            sides = [test.left] + list(test.comparators)
            return any(isinstance(s, ast.Name) and s.id in self.params
                       for s in sides)
        if isinstance(test, ast.BoolOp):
            return any(self._test_touches_param(v) for v in test.values)
        return False

    def visit_If(self, node):
        if self._test_touches_param(node.test):
            self._flag("MX203", "Python `if` on a function argument that "
                       "may be traced", node)
        self.generic_visit(node)

    def visit_While(self, node):
        if self._test_touches_param(node.test):
            self._flag("MX203", "Python `while` on a function argument "
                       "that may be traced", node)
        self.generic_visit(node)

    def visit_For(self, node):
        # MX313: a per-leaf loop over a gradient pytree whose body pulls
        # host values (float()/int(), .item()/.tolist()/.asnumpy(),
        # numpy.*) — per-parameter host round-trips every step, the shape
        # the in-graph health stats engine replaces. One finding per loop;
        # pure-jnp per-leaf loops (unrolled at trace) stay clean.
        if _mentions_grad(node.iter):
            hit = None
            for stmt in node.body:
                for sub in ast.walk(stmt):
                    if not isinstance(sub, ast.Call):
                        continue
                    f = sub.func
                    dotted = _dotted(f, self.scan.imports)
                    if dotted is not None and (
                            dotted == "numpy"
                            or dotted.startswith("numpy.")):
                        hit = sub
                    elif isinstance(f, ast.Attribute) and not sub.args \
                            and f.attr in ("item", "tolist", "asnumpy"):
                        hit = sub
                    elif isinstance(f, ast.Name) and sub.args \
                            and f.id in ("float", "int"):
                        hit = sub
                    if hit is not None:
                        break
                if hit is not None:
                    break
            if hit is not None:
                self._flag(
                    "MX313",
                    "per-leaf loop over a gradient pytree materializes "
                    "host statistics inside traced code (one device "
                    "round-trip per parameter per step); the in-graph "
                    "health stats engine computes these fused on device",
                    hit)
        self.generic_visit(node)

    def visit_JoinedStr(self, node):
        self._flag("MX302", "f-string inside traced code", node)
        # no generic_visit: one finding per f-string


# -- MX306: un-barriered wall-clock deltas around device dispatch -------------
# The timing footgun: `t0 = time.time(); out = step(x); dt = time.time()-t0`
# measures ENQUEUE cost under async dispatch, not execution. The scan is
# function-local and zero-FP-biased: it only fires when a time.time()/
# perf_counter() start is subtracted later in the same function, actual
# work (a non-trivial call) happens between, and nothing in between is
# barrier-shaped. time.monotonic() is exempt (deadline/backoff bookkeeping,
# never a measurement), as are telemetry/ and utils/profiler — the two
# sanctioned homes for timing.

_WALL_CLOCK_CALLS = ("time.time", "time.perf_counter")
# call-name fragments treated as blocking before the clock is read
_TIMING_BARRIER_PARTS = ("block", "barrier", "wait", "sync", "join",
                         "result", "asnumpy", "compile", "ready")
# calls that are not "work being timed" on their own
_TIMING_TRIVIAL_CALLS = {
    "len", "min", "max", "int", "float", "str", "abs", "round", "sorted",
    "sum", "isinstance", "getattr", "setattr", "hasattr", "repr", "next",
    "iter", "enumerate", "zip", "range", "list", "dict", "tuple", "set",
    "print", "format", "debug", "info", "warning", "error", "exception",
    "log", "append", "items", "keys", "values", "get", "pop", "update",
}


def _exempt_timing_path(path: str) -> bool:
    p = path.replace(os.sep, "/")
    return "/telemetry/" in p or p.endswith("utils/profiler.py") or \
        p.endswith("telemetry/__init__.py")


def _is_wall_clock_call(node, imports):
    if not isinstance(node, ast.Call):
        return False
    dotted = _dotted(node.func, imports)
    return dotted in _WALL_CLOCK_CALLS


class _FnTimingScan(ast.NodeVisitor):
    """One function body: clock-start assignments, barrier/work call lines,
    and clock-delta expressions. Nested defs/lambdas are their own scope
    and are skipped (the driver visits them separately)."""

    def __init__(self, imports):
        self.imports = imports
        self.assigns = {}        # name -> latest assignment lineno
        self.barrier_lines = []
        self.work_lines = []
        self.deltas = []         # (lineno, col, start_name)

    def visit_FunctionDef(self, node):  # separate scope
        pass

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_Lambda = visit_FunctionDef

    def visit_Assign(self, node):
        if len(node.targets) == 1 and isinstance(node.targets[0], ast.Name) \
                and _is_wall_clock_call(node.value, self.imports):
            self.assigns[node.targets[0].id] = node.lineno
        else:
            self.generic_visit(node)

    def visit_Call(self, node):
        f = node.func
        name = f.attr if isinstance(f, ast.Attribute) else \
            getattr(f, "id", "")
        lname = name.lower()
        if _is_wall_clock_call(node, self.imports):
            pass  # reading the clock is not the work being timed
        elif any(part in lname for part in _TIMING_BARRIER_PARTS):
            self.barrier_lines.append(node.lineno)
        elif name and name not in _TIMING_TRIVIAL_CALLS:
            self.work_lines.append(node.lineno)
        self.generic_visit(node)

    def visit_BinOp(self, node):
        if isinstance(node.op, ast.Sub) and isinstance(node.right, ast.Name):
            left_ok = _is_wall_clock_call(node.left, self.imports) or (
                isinstance(node.left, ast.Name)
                and node.left.id in self.assigns)
            if left_ok and node.right.id in self.assigns:
                self.deltas.append((node.lineno, node.col_offset,
                                    node.right.id))
        self.generic_visit(node)


def _scan_unbarriered_timing(tree, path, imports, findings):
    if _exempt_timing_path(path):
        return
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        scan = _FnTimingScan(imports)
        for stmt in fn.body:
            scan.visit(stmt)
        for lineno, col, start in scan.deltas:
            l0 = scan.assigns.get(start)
            if l0 is None or l0 >= lineno:
                continue
            worked = any(l0 < l < lineno for l in scan.work_lines)
            barriered = any(l0 < l < lineno for l in scan.barrier_lines)
            if worked and not barriered:
                findings.append(Finding(
                    get_rule("MX306"),
                    f"wall-clock delta `... - {start}` times dispatched "
                    "work with no barrier between start and read",
                    path=path, line=lineno, col=col))


# -- MX307: leaked StepTimeline spans / phases --------------------------------
# A span that is opened but not closed on every path poisons the trace:
# later phase() calls attach to the dead step and the cross-rank merge
# sees unterminated/overlapping spans. The scan is function-local and
# zero-FP-biased: it flags (a) a `<x>.begin_step(...)` result bound to a
# name on which `.end()` is never called anywhere in the same function
# (spans used as `with` context managers are fine — __exit__ ends them),
# (b) a bare-expression `begin_step(...)` whose span can never be ended,
# and (c) a bare-expression `telemetry.phase(...)`/`timed(...)` call —
# those return context managers; calling without `with` records nothing
# and is always a bug. telemetry/ itself (the primitives' home) is exempt.

_SPAN_OPENERS = ("begin_step",)
_CM_TIMERS = ("phase", "timed")


def _call_attr_name(node):
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    return f.attr if isinstance(f, ast.Attribute) else getattr(f, "id", None)


class _FnSpanScan(ast.NodeVisitor):
    """One function body: span-opening assignments, .end() calls, with-
    managed opens, and bare context-manager-returning calls. Nested defs
    are their own scope (the driver visits them separately)."""

    def __init__(self):
        self.opened = {}       # name -> lineno of `x = ....begin_step(...)`
        self.ended = set()     # names with `.end(` called on them
        self.bare = []         # (lineno, col, what) immediate findings

    def visit_FunctionDef(self, node):  # separate scope
        pass

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_Lambda = visit_FunctionDef

    def _record_open(self, target, value):
        """Bind span-opening call results (looking through ternaries:
        `span = tl.begin_step(...) if tl else None`)."""
        for v in ([value.body, value.orelse]
                  if isinstance(value, ast.IfExp) else [value]):
            if _call_attr_name(v) in _SPAN_OPENERS and \
                    isinstance(target, ast.Name):
                self.opened[target.id] = (v.lineno, v.col_offset)

    def visit_Assign(self, node):
        if len(node.targets) == 1:
            self._record_open(node.targets[0], node.value)
        self.generic_visit(node)

    def visit_With(self, node):
        # `with tl.begin_step(...) [as span]:` — __exit__ closes it
        self.generic_visit(node)

    visit_AsyncWith = visit_With

    def visit_Expr(self, node):
        name = _call_attr_name(node.value)
        if name in _SPAN_OPENERS:
            self.bare.append((node.lineno, node.col_offset,
                              "span from bare `begin_step(...)` call is "
                              "discarded and can never be ended"))
        elif name in _CM_TIMERS:
            self.bare.append((node.lineno, node.col_offset,
                              f"`{name}(...)` returns a context manager; "
                              "calling it without `with` records nothing"))
        self.generic_visit(node)

    def visit_Call(self, node):
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "end" and \
                isinstance(f.value, ast.Name):
            self.ended.add(f.value.id)
        self.generic_visit(node)


def _with_bound_names(fn):
    """Names bound by `with ... as <name>` anywhere in the function —
    `with tl.begin_step(...) as span:` closes span via __exit__, and an
    extra span.end() is not required."""
    names = set()
    for sub in ast.walk(fn):
        if isinstance(sub, (ast.With, ast.AsyncWith)):
            for item in sub.items:
                if isinstance(item.optional_vars, ast.Name):
                    names.add(item.optional_vars.id)
    return names


def _scan_leaked_spans(tree, path, findings):
    if _exempt_timing_path(path):
        return
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        scan = _FnSpanScan()
        for stmt in fn.body:
            scan.visit(stmt)
        for lineno, col, what in scan.bare:
            findings.append(Finding(get_rule("MX307"), what,
                                    path=path, line=lineno, col=col))
        with_names = None
        for name, (lineno, col) in scan.opened.items():
            if name in scan.ended:
                continue
            if with_names is None:
                with_names = _with_bound_names(fn)
            if name in with_names:
                continue
            findings.append(Finding(
                get_rule("MX307"),
                f"span `{name}` opened with begin_step() but `.end()` is "
                "never called in this function (leaked spans poison the "
                "cross-rank merge)",
                path=path, line=lineno, col=col))


# -- MX309: implicit host syncs inside step loops -----------------------------
# The silent killer of both async dispatch and memory accounting: a loop
# that dispatches the fused step AND pulls values to host every iteration
# (`.asnumpy()`, `.item()`, `np.asarray(...)`) serializes the pipeline —
# each pull blocks on the in-flight program, so the comm/compute overlap
# schedule (PR 7) degenerates to lockstep and the live-array ledger sees
# phantom transient host copies. The scan is loop-local and zero-FP-biased:
# it only fires inside a for/while loop that visibly dispatches a step (a
# call whose name contains "step", or forward()/backward()), and only on
# the unambiguous sync shapes. Intentional per-step syncs (guard verdicts,
# host-metric paths) carry `# mxlint: disable=MX309` with a justification.
# telemetry/ and utils/profiler are exempt, as for MX306/307.

_STEP_DISPATCH_PARTS = ("step",)
_STEP_DISPATCH_EXACT = ("forward", "backward")
_HOST_PULL_ATTRS = ("asnumpy", "item")
_HOST_PULL_NUMPY = ("numpy.asarray", "numpy.array", "numpy.ascontiguousarray")


def _is_step_dispatch(node):
    name = _call_attr_name(node)
    if not name:
        return False
    lname = name.lower()
    return lname in _STEP_DISPATCH_EXACT or \
        any(part in lname for part in _STEP_DISPATCH_PARTS)


def _iter_loop_body_nodes(loop):
    """Walk a loop's immediate body: nested defs/lambdas are their own
    scope and nested loops are their own *step loop* (each is judged on
    its own dispatch) — so a once-per-epoch pull after an inner batch
    loop is not blamed on the steps inside it."""
    stack = list(loop.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.For, ast.AsyncFor, ast.While)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _scan_step_loop_syncs(tree, path, imports, findings):
    if _exempt_timing_path(path):
        return
    seen = set()  # (line, col): overlapping scopes must not double-report
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
            continue
        calls = [n for n in _iter_loop_body_nodes(loop)
                 if isinstance(n, ast.Call)]
        if not any(_is_step_dispatch(c) for c in calls):
            continue
        for call in calls:
            loc = (call.lineno, call.col_offset)
            if loc in seen:
                continue
            f = call.func
            if isinstance(f, ast.Attribute) and \
                    f.attr in _HOST_PULL_ATTRS and not call.args:
                seen.add(loc)
                findings.append(Finding(
                    get_rule("MX309"),
                    f"`.{f.attr}()` inside a step-dispatching loop blocks "
                    "the host on a device transfer every iteration",
                    path=path, line=call.lineno, col=call.col_offset))
                continue
            dotted = _dotted(f, imports)
            if dotted in _HOST_PULL_NUMPY:
                seen.add(loc)
                findings.append(Finding(
                    get_rule("MX309"),
                    f"`{dotted}(...)` inside a step-dispatching loop "
                    "forces a device-to-host copy every iteration",
                    path=path, line=call.lineno, col=call.col_offset))
                continue
            # float(x)/int(x) on a bare name: the classic scalar pull
            # (loss = float(out)); attribute/subscript args stay exempt —
            # shapes/pads etc. are host metadata, not device values
            if isinstance(f, ast.Name) and f.id in ("float", "int") and \
                    len(call.args) == 1 and \
                    isinstance(call.args[0], ast.Name):
                seen.add(loc)
                findings.append(Finding(
                    get_rule("MX309"),
                    f"`{f.id}({call.args[0].id})` inside a "
                    "step-dispatching loop forces a scalar device-to-host "
                    "sync every iteration",
                    path=path, line=call.lineno, col=call.col_offset))


# -- MX310: world-size literals frozen into closures --------------------------
# The elastic-staleness bug class (ISSUE 10): `ndev = 8` in an outer scope,
# captured by a nested step/placement function — after a mid-run resize the
# closure keeps computing with the dead world's size. The scan is
# function-local and zero-FP-biased: it fires only when (a) an enclosing
# function binds a world/axis-size-NAMED variable to an INTEGER LITERAL and
# (b) a nested def/lambda reads that name as a free variable. Sizes derived
# from live objects (`int(mesh.shape["dp"])`, `kv.num_workers`,
# `coordinator.world_size`) are call results, not literals, so the healthy
# idiom never flags. The mesh/coordinator providers themselves
# (parallel/mesh.py, resilience/elastic.py) are exempt — defining the world
# is their job.

_WORLD_SIZE_NAMES = frozenset({
    "world_size", "num_workers", "axis_size", "ndev", "num_devices",
    "n_workers", "n_devices", "nproc"})
_MX310_EXEMPT_FILES = ("mesh.py", "elastic.py")


def _scan_world_literal_closures(tree, path, findings):
    base = os.path.basename(os.path.normpath(path))
    if base in _MX310_EXEMPT_FILES:
        return
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # world-size names this scope binds to plain integer literals
        # (only statements local to fn — nested defs are their own scope)
        literal_bound = {}
        for node in _iter_local_nodes(fn):
            targets = []
            if isinstance(node, ast.Assign):
                targets = [t for t in node.targets if isinstance(t, ast.Name)]
                value = node.value
            elif isinstance(node, ast.AnnAssign) and \
                    isinstance(node.target, ast.Name):
                targets = [node.target]
                value = node.value
            else:
                continue
            if not (isinstance(value, ast.Constant)
                    and type(value.value) is int):
                continue
            for t in targets:
                if t.id.lower() in _WORLD_SIZE_NAMES:
                    literal_bound[t.id] = node.lineno
        if not literal_bound:
            continue
        for nested in ast.walk(fn):
            if nested is fn or not isinstance(
                    nested, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
                continue
            a = nested.args
            bound_inner = {p.arg for p in a.args + a.posonlyargs
                           + a.kwonlyargs}
            if a.vararg is not None:
                bound_inner.add(a.vararg.arg)
            if a.kwarg is not None:
                bound_inner.add(a.kwarg.arg)
            for sub in ast.walk(nested):
                if isinstance(sub, ast.Name) and \
                        isinstance(sub.ctx, ast.Store):
                    bound_inner.add(sub.id)
            for sub in ast.walk(nested):
                if isinstance(sub, ast.Name) and \
                        isinstance(sub.ctx, ast.Load) and \
                        sub.id in literal_bound and \
                        sub.id not in bound_inner:
                    findings.append(Finding(
                        get_rule("MX310"),
                        f"closure captures `{sub.id}` bound to an integer "
                        f"literal at line {literal_bound[sub.id]} — a "
                        f"world/axis size frozen at build time goes stale "
                        f"when the elastic world resizes",
                        path=path, line=sub.lineno, col=sub.col_offset))
                    break  # one finding per closure is enough


# -- MX308: unpinned wire collectives in comm/ --------------------------------
# The convert-commuting bug class documented at comm/allreduce.py
# (_exchange): converting before/after pure data movement is elementwise-
# equivalent, so XLA freely commutes the encode/decode casts across a
# collective — the payload then crosses the wire at full precision with
# correct values and the compression silently lost. Every wire collective
# in comm/ must be bracketed by lax.optimization_barrier. The scan is
# function-local and zero-FP-biased: a collective call is flagged only
# when NO optimization_barrier call appears lexically before it, or none
# after it, within the same function (nested defs are their own scope).

_WIRE_COLLECTIVES = ("all_to_all", "all_gather", "psum_scatter")


def _comm_scoped(path: str) -> bool:
    return "comm" in os.path.normpath(path).split(os.sep)


def _iter_local_nodes(fn):
    """Walk a scope's body without descending into nested defs/lambdas
    (every def, lambda, and the module itself is scanned as its own
    scope by _scan_unpinned_collectives)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _scan_unpinned_collectives(tree, path, findings):
    if not _comm_scoped(path):
        return
    # every scope that can hold a collective call: defs, lambdas, and
    # module level — a collective is only excused by barriers in its OWN
    # scope, so a bare lambda or module-level call can't hide
    scopes = [tree] + [n for n in ast.walk(tree)
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef, ast.Lambda))]
    for fn in scopes:
        colls, barriers = [], []
        for sub in _iter_local_nodes(fn):
            if not isinstance(sub, ast.Call):
                continue
            name = sub.func.attr if isinstance(sub.func, ast.Attribute) \
                else getattr(sub.func, "id", None)
            if name in _WIRE_COLLECTIVES:
                colls.append((name, sub.lineno, sub.col_offset))
            elif name == "optimization_barrier":
                barriers.append(sub.lineno)
        for name, lineno, col in colls:
            pinned = any(ln <= lineno for ln in barriers) and \
                any(ln >= lineno for ln in barriers)
            if not pinned:
                findings.append(Finding(
                    get_rule("MX308"),
                    f"`{name}` has no optimization_barrier pinning on both "
                    "sides — XLA can commute the payload converts across "
                    "the collective (fp32 on the wire, compression lost)",
                    path=path, line=lineno, col=col))


# -- MX312: pallas kernel discipline ------------------------------------------
# Two shapes of the same drift (ISSUE 13): a `pl.pallas_call` emitted
# outside mxnet_tpu/ops/pallas/ escapes the kernel layer's registry,
# interpret-mode gate, and roofline accounting; a kernel module inside
# the layer that never calls registry.register_kernel leaves its kernel
# unpriced — the jaxpr auditor falls back to one-grid-cell recursion and
# the MFU/roofline numbers silently under-count. Zero-FP-biased: only
# literal `pallas_call` call sites fire, and in-layer modules are excused
# by ANY register_kernel call (the name<->model pairing is enforced by
# the parity/attribution tests, not the lint).


def _pallas_scoped(path: str) -> bool:
    parts = os.path.normpath(path).split(os.sep)
    return "pallas" in parts


def _scan_kernel_discipline(tree, path, findings):
    calls, registers = [], False
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = node.func.attr if isinstance(node.func, ast.Attribute) \
            else getattr(node.func, "id", None)
        if name == "pallas_call":
            calls.append(node)
        elif name == "register_kernel":
            registers = True
    if not calls:
        return
    if not _pallas_scoped(path):
        for node in calls:
            findings.append(Finding(
                get_rule("MX312"),
                "`pl.pallas_call` outside mxnet_tpu/ops/pallas/ — kernels "
                "live in the kernel layer (registry cost model, shared "
                "interpret gate, catalog + roofline rows)",
                path=path, line=node.lineno, col=node.col_offset))
        return
    if not registers:
        node = calls[0]
        findings.append(Finding(
            get_rule("MX312"),
            "kernel module emits pallas_call but never registers a "
            "FLOP/byte model (registry.register_kernel) — the jaxpr "
            "auditor and MFU accountant will under-count it",
            path=path, line=node.lineno, col=node.col_offset))


# -- MX311: fleet actuation outside the policy loop ---------------------------
# ISSUE 12: actuation must flow through resilience/controller.py so every
# membership/tier change carries the controller's safety rails (hysteresis,
# cooldowns, dry-run, breaker) and lands in the decision log. The scan is
# zero-FP-biased: `.request_world(` and `.set_gradient_compression(` are
# distinctive enough to flag anywhere in scope; `.kill(` only fires when
# the receiver's name says coordinator (`co`, `*coord*`, `*elastic*` —
# `os.kill` / `proc.kill` never match). Definition sites are exempt
# (controller.py IS the policy loop, elastic.py OWNS the lever), as are
# tests, examples, and lint fixtures; intentional out-of-loop sites carry
# `# mxlint: disable=MX311` with a justification.

_MX311_METHODS = frozenset({"kill", "request_world",
                            "set_gradient_compression"})
_MX311_EXEMPT_FILES = ("controller.py", "elastic.py")


def _mx311_exempt(path: str) -> bool:
    norm = os.path.normpath(path)
    parts = norm.split(os.sep)
    if any(p in ("tests", "examples", "fixtures") for p in parts):
        return True
    base = os.path.basename(norm)
    return base in _MX311_EXEMPT_FILES or base.startswith("test_")


def _mx311_receiver_is_coordinator(func: ast.Attribute) -> bool:
    recv = func.value
    name = None
    if isinstance(recv, ast.Name):
        name = recv.id
    elif isinstance(recv, ast.Attribute):
        name = recv.attr
    if name is None:
        return False
    low = name.lower()
    return low == "co" or "coord" in low or "elastic" in low


def _scan_fleet_actuation(tree, path, findings):
    if _mx311_exempt(path):
        return
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        name = node.func.attr
        if name not in _MX311_METHODS:
            continue
        if name == "kill" and \
                not _mx311_receiver_is_coordinator(node.func):
            continue  # os.kill / process.kill are not fleet actuation
        recv = node.func.value
        if isinstance(recv, ast.Call) and \
                getattr(recv.func, "id", None) == "super":
            continue  # an override delegating to its base is not a site
        findings.append(Finding(
            get_rule("MX311"),
            f"direct fleet actuation `.{name}(...)` outside "
            "resilience/controller.py — membership/compression-tier "
            "changes must flow through the FleetController policy loop "
            "(hysteresis, cooldowns, dry-run, breaker, decision log)",
            path=path, line=node.lineno, col=node.col_offset))


# -- MX314: raw jax.profiler captures outside the profiling layer -------------
# ISSUE 15: every capture flows through telemetry/profiling.py (hub events
# for the JSONL stream, soft failure on concurrent windows, `profile`
# badput pricing) or the utils/profiler wrappers over it. Two shapes of
# drift: (a) a literal `jax.profiler.start_trace/stop_trace/trace` call
# site outside the two owner modules; (b) ANY `start_trace(...)` call —
# the sanctioned wrapper included — in a function with no finally-guarded
# stop, which leaks a running process-global trace past the first
# exception. Zero-FP-biased: (a) only fires when the receiver is
# literally `jax.profiler` or a name bound by `from jax import profiler`;
# tests, examples, and fixtures are exempt.

_MX314_OWNER_FILES = ("profiler.py", "profiling.py")


def _mx314_exempt(path: str) -> bool:
    norm = os.path.normpath(path)
    parts = norm.split(os.sep)
    if any(p in ("tests", "examples", "fixtures") for p in parts):
        return True
    base = os.path.basename(norm)
    return base in _MX314_OWNER_FILES or base.startswith("test_")


def _is_jax_profiler_receiver(func: ast.Attribute, jp_names) -> bool:
    recv = func.value
    if isinstance(recv, ast.Attribute) and recv.attr == "profiler" and \
            isinstance(recv.value, ast.Name) and recv.value.id == "jax":
        return True  # jax.profiler.<x>
    return isinstance(recv, ast.Name) and recv.id in jp_names


def _scan_profiler_discipline(tree, path, findings):
    if _mx314_exempt(path):
        return
    jp_names = set()  # names bound by `from jax import profiler [as x]`
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "jax":
            for alias in node.names:
                if alias.name == "profiler":
                    jp_names.add(alias.asname or alias.name)
    flagged: set = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        if node.func.attr not in ("trace", "start_trace", "stop_trace"):
            continue
        if not _is_jax_profiler_receiver(node.func, jp_names):
            continue
        flagged.add(id(node))
        findings.append(Finding(
            get_rule("MX314"),
            f"raw `jax.profiler.{node.func.attr}` outside utils/profiler.py"
            " / telemetry/profiling.py — captures flow through "
            "telemetry.profiling (hub events, `profile` badput pricing, "
            "safe behavior under concurrent windows)",
            path=path, line=node.lineno, col=node.col_offset))

    # (b) start_trace/start_capture calls owned by their INNERMOST
    # function scope; a scope is clean when any finally block in IT stops
    # the trace. Nested defs always open a fresh scope — including defs
    # that sit inside a try/finally body, whose deferred bodies run long
    # after the outer finally fired.
    scope_starts: dict = {}
    scope_guarded: dict = {}

    def child_walk(child, scope, in_finally):
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
            walk(child, id(child), False)
        else:
            walk(child, scope, in_finally)

    def walk(node, scope, in_finally):
        if isinstance(node, ast.Call):
            f = node.func
            name = f.attr if isinstance(f, ast.Attribute) else \
                getattr(f, "id", None)
            if name in ("start_trace", "start_capture"):
                scope_starts.setdefault(scope, []).append((node, name))
            elif name in ("stop_trace", "stop_capture") and in_finally:
                scope_guarded[scope] = True
        if isinstance(node, ast.Try):
            for child in node.body + node.orelse + node.handlers:
                child_walk(child, scope, in_finally)
            for child in node.finalbody:
                child_walk(child, scope, True)
            return
        for child in ast.iter_child_nodes(node):
            child_walk(child, scope, in_finally)

    walk(tree, id(tree), False)
    for scope, calls in scope_starts.items():
        if scope_guarded.get(scope):
            continue
        for call, name in calls:
            if id(call) in flagged:
                continue  # already reported as a raw capture above
            findings.append(Finding(
                get_rule("MX314"),
                f"`{name}` without a finally-guarded stop in the same "
                "function — an exception leaks the process-global running "
                "trace and every later capture fails (use "
                "telemetry.profiling.capture(), or stop in a `finally`)",
                path=path, line=call.lineno, col=call.col_offset))


# -- MX315: direct sharded-checkpoint writes outside the checkpoint plane -----
# ISSUE 17: every durable write flows through utils/checkpoint.py (tmp-dir
# staging + CRC manifest + atomic rename) driven by resilience/ckpt_async.py
# (writer thread, flush barriers, keep-last-k GC, `checkpoint` badput
# pricing). A `save_sharded(...)` call anywhere else can interleave with an
# in-flight async write of the same step id and never shows up in the
# telemetry gauges. Zero-FP-biased: fires on the bare call names only
# (Name or Attribute receiver — `ckpt.save_sharded(...)` included); loads,
# reads and `load_resharded` never match; tests/examples/fixtures exempt.

_MX315_OWNER_FILES = ("checkpoint.py", "ckpt_async.py")
_MX315_WRITE_NAMES = ("save_sharded", "_save_sharded", "_write_manifest")


def _mx315_exempt(path: str) -> bool:
    norm = os.path.normpath(path)
    parts = norm.split(os.sep)
    if any(p in ("tests", "examples", "fixtures") for p in parts):
        return True
    base = os.path.basename(norm)
    return base in _MX315_OWNER_FILES or base.startswith("test_")


def _scan_checkpoint_discipline(tree, path, findings):
    if _mx315_exempt(path):
        return
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = f.attr if isinstance(f, ast.Attribute) else \
            getattr(f, "id", None)
        if name not in _MX315_WRITE_NAMES:
            continue
        findings.append(Finding(
            get_rule("MX315"),
            f"direct `{name}` outside utils/checkpoint.py / "
            "resilience/ckpt_async.py — the checkpoint plane owns "
            "durability ordering (tmp staging, CRC commit, retention GC, "
            "writer flush barriers) and the `checkpoint` badput pricing; "
            "route through ckpt_async.save_now or "
            "AsyncCheckpointWriter.submit",
            path=path, line=node.lineno, col=node.col_offset))


# -- MX316: run-ledger discipline (ISSUE 20) ----------------------------------
# Every RunRecord flows through telemetry/ledger.py: distill() owns the
# schema, append_record() the atomic one-file-per-record write (tmp +
# rename + CRC sidecar via utils.checkpoint.atomic_write) and the
# `run_summary` announcement event. A module that reads
# MXNET_TPU_LEDGER_DIR itself (to write its own files there) or emits its
# own `run_summary` events produces history the trend/compare gates cannot
# read. Zero-FP-biased: fires only on (a) an `emit`/`.emit` call whose
# first positional argument is the literal "run_summary", and (b) an
# os.environ get/[] whose key is the literal "MXNET_TPU_LEDGER_DIR" —
# `monkeypatch.setenv` and docstrings never match; owner + tests exempt.

_MX316_OWNER_FILES = ("ledger.py",)
_MX316_ENV_KEY = "MXNET_TPU_LEDGER_DIR"
_MX316_ENV_GETTERS = ("get", "getenv", "pop", "setdefault")


def _mx316_exempt(path: str) -> bool:
    norm = os.path.normpath(path)
    parts = norm.split(os.sep)
    if any(p in ("tests", "examples", "fixtures") for p in parts):
        return True
    base = os.path.basename(norm)
    return base in _MX316_OWNER_FILES or base.startswith("test_")


def _const_eq(node, value) -> bool:
    return isinstance(node, ast.Constant) and node.value == value


def _scan_ledger_discipline(tree, path, findings):
    if _mx316_exempt(path):
        return
    for node in ast.walk(tree):
        if isinstance(node, ast.Subscript):
            # os.environ["MXNET_TPU_LEDGER_DIR"] in any read/write position
            if _const_eq(getattr(node, "slice", None), _MX316_ENV_KEY):
                findings.append(Finding(
                    get_rule("MX316"),
                    f"direct `{_MX316_ENV_KEY}` subscript outside "
                    "telemetry/ledger.py — resolve the store through "
                    "telemetry.ledger.ledger_dir() so every record lands "
                    "via the atomic CRC'd writer",
                    path=path, line=node.lineno, col=node.col_offset))
            continue
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = f.attr if isinstance(f, ast.Attribute) else \
            getattr(f, "id", None)
        if name == "emit" and node.args and \
                _const_eq(node.args[0], "run_summary"):
            findings.append(Finding(
                get_rule("MX316"),
                "hand-rolled `run_summary` emission outside "
                "telemetry/ledger.py — the ledger announces each append "
                "itself (append_record); a duplicate summary event skews "
                "the golden-key stream and incident counts",
                path=path, line=node.lineno, col=node.col_offset))
        elif name in _MX316_ENV_GETTERS and node.args and \
                _const_eq(node.args[0], _MX316_ENV_KEY):
            findings.append(Finding(
                get_rule("MX316"),
                f"direct `{_MX316_ENV_KEY}` consultation outside "
                "telemetry/ledger.py — resolve the store through "
                "telemetry.ledger.ledger_dir() (one writer, one reader "
                "discipline; see telemetry/ledger.py)",
                path=path, line=node.lineno, col=node.col_offset))


# calls whose presence inside a retry loop counts as bounding it: anything
# sleep/backoff/wait-shaped (time.sleep, policy backoff, cv.wait_for, ...)
_BOUNDING_CALL_PARTS = ("sleep", "backoff", "wait", "delay", "retry_call",
                        "monotonic", "deadline")


def _is_bounding_call(node: ast.Call) -> bool:
    name = None
    if isinstance(node.func, ast.Attribute):
        name = node.func.attr
    elif isinstance(node.func, ast.Name):
        name = node.func.id
    return name is not None and \
        any(part in name.lower() for part in _BOUNDING_CALL_PARTS)


def _handler_escapes(handler: ast.ExceptHandler) -> bool:
    """True when the handler leaves the loop (raise/return/break at its
    top level) — that's failure propagation, not a retry."""
    return any(isinstance(s, (ast.Raise, ast.Return, ast.Break))
               for s in handler.body)


def _handler_is_swallow(handler: ast.ExceptHandler) -> bool:
    """True when the handler does nothing but spin: only pass/continue/
    logging — the shape of a blind retry. Handlers doing real work (e.g.
    replying on a socket) are an event loop, not a retry loop."""
    for s in handler.body:
        if isinstance(s, (ast.Pass, ast.Continue)):
            continue
        if isinstance(s, ast.Expr) and isinstance(s.value, ast.Call):
            f = s.value.func
            name = f.attr if isinstance(f, ast.Attribute) else \
                getattr(f, "id", "")
            if name in ("debug", "info", "warning", "error", "exception",
                        "print", "log"):
                continue
        return False
    return True


def _scan_robustness(tree: ast.AST, path: str, findings: list):
    """MX601 bare excepts; MX602 unbounded retry loops (while True +
    exception-swallowing handler + no sleep/backoff/deadline in the loop)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            findings.append(Finding(
                get_rule("MX601"), "bare `except:` clause",
                path=path, line=node.lineno, col=node.col_offset))
        if isinstance(node, ast.While) and \
                isinstance(node.test, ast.Constant) and node.test.value is True:
            bounded = any(isinstance(sub, ast.Call) and _is_bounding_call(sub)
                          for sub in ast.walk(node))
            if bounded:
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Try):
                    retrying = [h for h in sub.handlers
                                if not _handler_escapes(h)
                                and _handler_is_swallow(h)]
                    if retrying:
                        findings.append(Finding(
                            get_rule("MX602"),
                            "`while True` retry loop swallows exceptions "
                            "with no backoff/deadline/attempt bound",
                            path=path, line=node.lineno,
                            col=node.col_offset))
                        break


# -- MX805: sharding placement outside the parallel/comm owner layers ---------
# ISSUE 16 (Pass 5 source rule): placement decisions — raw
# `with_sharding_constraint` and `device_put(x, NamedSharding(...))` —
# must live in parallel/ or comm/, where the partitioner and the comm
# plan can account for them. A stray constraint elsewhere silently
# changes the lowered collective set out from under the MX802
# reconciliation. Intentional sites (checkpoint restore, model
# placement helpers) carry `# mxlint: disable=MX805` with a reason.

_MX805_OWNER_DIRS = ("parallel", "comm")


def _mx805_exempt(path: str) -> bool:
    norm = os.path.normpath(path)
    parts = norm.split(os.sep)
    if any(p in ("tests", "examples", "fixtures") for p in parts):
        return True
    if any(p in _MX805_OWNER_DIRS for p in parts[:-1]):
        return True
    return os.path.basename(norm).startswith("test_")


def _call_name(func) -> str:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


def _contains_namedsharding(node) -> bool:
    return any(isinstance(sub, ast.Call)
               and _call_name(sub.func) == "NamedSharding"
               for sub in ast.walk(node))


def _scan_placement_discipline(tree, path, findings):
    if _mx805_exempt(path):
        return
    # names assigned from any expression that builds a NamedSharding —
    # covers `sh = NamedSharding(...)`, dict/list comprehensions of them,
    # and `shardings = {k: NamedSharding(...) for ...}` later subscripted
    sharding_names: set = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Assign, ast.AnnAssign)) and \
                node.value is not None and \
                _contains_namedsharding(node.value):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                if isinstance(t, ast.Name):
                    sharding_names.add(t.id)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node.func)
        if name == "with_sharding_constraint":
            findings.append(Finding(
                get_rule("MX805"),
                "raw `with_sharding_constraint` outside parallel//comm/ "
                "— placement belongs to the partitioner so the comm plan "
                "(and the MX802 reconciliation) can account for it",
                path=path, line=node.lineno, col=node.col_offset))
            continue
        if name != "device_put":
            continue
        dst = None
        if len(node.args) >= 2:
            dst = node.args[1]
        else:
            for kw in node.keywords:
                if kw.arg == "device":
                    dst = kw.value
        if dst is None:
            continue
        placed = _contains_namedsharding(dst)
        if isinstance(dst, ast.Name) and dst.id in sharding_names:
            placed = True
        if isinstance(dst, ast.Subscript) and \
                isinstance(dst.value, ast.Name) and \
                dst.value.id in sharding_names:
            placed = True
        if placed:
            findings.append(Finding(
                get_rule("MX805"),
                "`device_put` onto a NamedSharding outside "
                "parallel//comm/ — sharded placement belongs to the "
                "owner layers the comm plan audits",
                path=path, line=node.lineno, col=node.col_offset))


def _suppressed(finding: Finding, lines: list[str]) -> bool:
    if not 1 <= finding.line <= len(lines):
        return False
    line = lines[finding.line - 1]
    if "# mxlint:" not in line:
        return False
    pragma = line.split("# mxlint:", 1)[1].strip()
    if pragma.startswith("disable"):
        _, _, ids = pragma.partition("=")
        if not ids.strip():
            return True
        # `disable=MX704 - justification` / `disable=MX701,MX704 reason`:
        # an id token ends at the first whitespace, so an inline
        # justification (the MX70x audit-record discipline) parses clean
        tokens = set()
        for part in ids.split(","):
            part = part.strip()
            if part:
                tokens.add(part.split()[0])
        return finding.rule.id in tokens
    return False


def lint_source(text: str, path: str = "<string>") -> list[Finding]:
    """Lint one Python source string; returns findings (pragma-filtered)."""
    lines = text.splitlines()
    if any("# mxlint: skip-file" in ln for ln in lines[:5]):
        return []
    try:
        tree = ast.parse(text, filename=path)
    except SyntaxError as e:
        f = Finding(get_rule("MX100"),
                    f"file does not parse: {e.msg}", path=path,
                    line=e.lineno or 0, col=e.offset or 0)
        return [f]

    scan = _ModuleScan(path)
    scan.visit(tree)
    _scan_robustness(tree, path, scan.findings)
    _scan_unbarriered_timing(tree, path, scan.imports, scan.findings)
    _scan_leaked_spans(tree, path, scan.findings)
    _scan_unpinned_collectives(tree, path, scan.findings)
    _scan_step_loop_syncs(tree, path, scan.imports, scan.findings)
    _scan_world_literal_closures(tree, path, scan.findings)
    _scan_fleet_actuation(tree, path, scan.findings)
    _scan_kernel_discipline(tree, path, scan.findings)
    _scan_profiler_discipline(tree, path, scan.findings)
    _scan_checkpoint_discipline(tree, path, scan.findings)
    _scan_ledger_discipline(tree, path, scan.findings)
    _scan_placement_discipline(tree, path, scan.findings)

    roots: list[ast.AST] = list(scan.traced_lambdas)
    roots += [d for d in scan.defs if d.name in scan.traced_names]
    visited: set[int] = set()
    for root in roots:
        if id(root) in visited:
            continue
        for sub in ast.walk(root):
            visited.add(id(sub))
        args = root.args
        params = {a.arg for a in args.args if a.arg not in ("self", "cls")}
        params.update(a.arg for a in args.kwonlyargs)
        _TracedWalk(scan, params).visit(
            root if isinstance(root, ast.Lambda) else ast.Module(
                body=root.body, type_ignores=[]))

    return [f for f in scan.findings if not _suppressed(f, lines)]


def lint_file(path: str) -> list[Finding]:
    with open(path, encoding="utf-8") as f:
        return lint_source(f.read(), path)


def iter_python_files(paths):
    """Expand files/directories into .py files, deterministic order."""
    for p in paths:
        if os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = sorted(d for d in dirnames
                                     if d not in _SKIP_DIRS)
                for name in sorted(filenames):
                    if name.endswith(".py"):
                        yield os.path.join(dirpath, name)
        else:
            yield p


def lint_paths(paths) -> list[Finding]:
    findings = []
    for f in iter_python_files(paths):
        if f.endswith(".py"):
            findings.extend(lint_file(f))
    return findings
