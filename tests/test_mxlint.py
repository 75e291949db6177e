"""mxlint tier: seeded violations produce exactly the expected rule ids,
the repo itself lints clean (THE self-lint gate: this test runs in tier-1
on every PR), and Symbol.verify enforces the StaticGraph::InferShape
contract at bind time (ISSUE 1 acceptance criteria)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.analysis import lint_source, verify_json, verify_symbol
from mxnet_tpu.base import MXNetError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ids(findings):
    return [f.rule.id for f in findings]


# -- Pass 1: source lint fixtures ---------------------------------------------

def test_fixture_syntax_error_is_mx100():
    findings = lint_source("def broken(:\n", "fx.py")
    assert _ids(findings) == ["MX100"]
    assert findings[0].is_error


def test_fixture_bad_import():
    findings = lint_source("from jax import shard_map\n", "fx.py")
    assert _ids(findings) == ["MX101"]
    assert findings[0].is_error


def test_fixture_bad_import_experimental_path():
    src = "from jax.experimental.shard_map import shard_map\n"
    assert _ids(lint_source(src, "fx.py")) == ["MX101"]


def test_fixture_item_in_jitted_fn():
    src = (
        "import jax\n"
        "\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.item()\n"
    )
    findings = lint_source(src, "fx.py")
    assert _ids(findings) == ["MX202"]
    assert findings[0].line == 5


def test_fixture_host_sync_via_tracing_call():
    src = (
        "import jax\n"
        "from jax import lax\n"
        "def body(c, x):\n"
        "    return c + float(x), None\n"
        "def run(xs):\n"
        "    return lax.scan(body, 0.0, xs)\n"
    )
    assert _ids(lint_source(src, "fx.py")) == ["MX202"]


def test_fixture_numpy_in_shard_map_body():
    src = (
        "import numpy as np\n"
        "from mxnet_tpu.compat import shard_map\n"
        "def block(x):\n"
        "    return np.sum(x)\n"
        "def run(mesh, spec, x):\n"
        "    return shard_map(block, mesh=mesh, in_specs=spec,\n"
        "                     out_specs=spec)(x)\n"
    )
    assert _ids(lint_source(src, "fx.py")) == ["MX201"]


def test_fixture_static_argnums_list():
    src = (
        "import jax\n"
        "def g(x, n):\n"
        "    return x\n"
        "h = jax.jit(g, static_argnums=[1])\n"
    )
    assert _ids(lint_source(src, "fx.py")) == ["MX301"]


def test_fixture_mx303_jit_inside_loop():
    src = (
        "import jax\n"
        "def train(batches):\n"
        "    for b in batches:\n"
        "        step = jax.jit(lambda x: x * 2)\n"
        "        step(b)\n"
    )
    assert "MX303" in _ids(lint_source(src, "fx.py"))


def test_fixture_mx303_immediate_jit_call():
    src = (
        "import jax\n"
        "def f(g, x):\n"
        "    return jax.jit(g)(x)\n"
    )
    findings = lint_source(src, "fx.py")
    assert _ids(findings) == ["MX303"]
    assert "fresh jit wrapper" in findings[0].message


def test_fixture_mx303_unstable_static_args():
    src = (
        "import jax\n"
        "def g(x, n):\n"
        "    return x\n"
        "h = jax.jit(g, static_argnums=list(range(1, 2)))\n"
    )
    assert _ids(lint_source(src, "fx.py")) == ["MX303"]
    src2 = (
        "import jax\n"
        "def g(x, n):\n"
        "    return x\n"
        "h = jax.jit(g, static_argnames=[n for n in ('n',)])\n"
    )
    assert _ids(lint_source(src2, "fx.py")) == ["MX303"]


def test_fixture_mx303_clean_patterns_pass():
    """The sanctioned shapes: wrapper cached at module/instance scope,
    tuple static args — no findings."""
    src = (
        "import jax\n"
        "def g(x, n):\n"
        "    return x\n"
        "step = jax.jit(g, static_argnums=(1,))\n"
        "def train(batches):\n"
        "    for b in batches:\n"
        "        step(b, 2)\n"
    )
    assert _ids(lint_source(src, "fx.py")) == []


def test_fixture_mx303_pragma_suppression():
    src = (
        "import jax\n"
        "def f(g, x):\n"
        "    return jax.jit(g)(x)  # mxlint: disable=MX303\n"
    )
    assert _ids(lint_source(src, "fx.py")) == []


def test_fixture_fstring_in_traced_fn():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    name = f'shape={x.shape}'\n"
        "    return x\n"
    )
    assert _ids(lint_source(src, "fx.py")) == ["MX302"]


def test_callback_bodies_are_exempt():
    """numpy inside a pure_callback host fn is correct, not a hazard."""
    src = (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    def cb(a):\n"
        "        return np.asarray(a) * 2\n"
        "    return jax.pure_callback(cb, x, x)\n"
    )
    assert _ids(lint_source(src, "fx.py")) == []


def test_pragma_suppression():
    src = "from jax import shard_map  # mxlint: disable=MX101\n"
    assert lint_source(src, "fx.py") == []
    src2 = "# mxlint: skip-file\nfrom jax import shard_map\n"
    assert lint_source(src2, "fx.py") == []
    # pragma for a different rule does NOT suppress
    src3 = "from jax import shard_map  # mxlint: disable=MX202\n"
    assert _ids(lint_source(src3, "fx.py")) == ["MX101"]


# -- MX304: raw gradient psum outside the comm subsystem (ISSUE 4) ------------

def test_fixture_mx304_direct_psum_on_grads():
    src = (
        "import jax\n"
        "from jax import lax\n"
        "def sync(grads, ax):\n"
        "    return lax.psum(grads, ax)\n"
    )
    findings = lint_source(src, "fx.py")
    assert _ids(findings) == ["MX304"]
    assert not findings[0].is_error  # perf warning, not a gate


def test_fixture_mx304_tree_map_lambda_psum():
    src = (
        "import jax\n"
        "from jax import lax\n"
        "def sync(grads, ax):\n"
        "    return jax.tree_util.tree_map(\n"
        "        lambda g: lax.psum(g, ax), grads)\n"
    )
    assert _ids(lint_source(src, "fx.py")) == ["MX304"]


def test_fixture_mx304_clean_patterns():
    # psum of a scalar constant (axis-size probe) is not gradient traffic
    src = (
        "from jax import lax\n"
        "def axis_size(ax):\n"
        "    return lax.psum(1, ax)\n"
    )
    assert _ids(lint_source(src, "fx.py")) == []
    # the comm package is the sanctioned home for raw gradient psums
    src2 = (
        "import jax\n"
        "from jax import lax\n"
        "def sync(grads, ax):\n"
        "    return lax.psum(grads, ax)\n"
    )
    assert _ids(lint_source(src2, "mxnet_tpu/comm/allreduce.py")) == []
    # pragma suppression works like every other rule
    src3 = (
        "import jax\n"
        "from jax import lax\n"
        "def sync(grads, ax):\n"
        "    return lax.psum(grads, ax)  # mxlint: disable=MX304\n"
    )
    assert _ids(lint_source(src3, "fx.py")) == []


# -- MX6xx robustness fixtures (ISSUE 2 satellite) ----------------------------

def test_fixture_bare_except_is_mx601():
    src = "try:\n    risky()\nexcept:\n    pass\n"
    findings = lint_source(src, "fx.py")
    assert _ids(findings) == ["MX601"]
    assert findings[0].is_error and findings[0].line == 3


def test_fixture_unbounded_retry_loop_is_mx602():
    src = (
        "def send(op):\n"
        "    while True:\n"
        "        try:\n"
        "            return op()\n"
        "        except ConnectionError:\n"
        "            continue\n"
    )
    findings = lint_source(src, "fx.py")
    assert _ids(findings) == ["MX602"]
    assert findings[0].is_error


def test_fixture_bounded_retry_loops_are_clean():
    # backoff sleep bounds it
    src = (
        "import time\n"
        "def send(op):\n"
        "    while True:\n"
        "        try:\n"
        "            return op()\n"
        "        except ConnectionError:\n"
        "            time.sleep(0.1)\n"
    )
    assert _ids(lint_source(src, "fx.py")) == []
    # a handler that escapes the loop is failure propagation, not a retry
    src2 = (
        "def serve(op):\n"
        "    while True:\n"
        "        try:\n"
        "            op()\n"
        "        except OSError:\n"
        "            return\n"
    )
    assert _ids(lint_source(src2, "fx.py")) == []
    # real work in the handler (e.g. replying on a socket) is an event
    # loop, not a blind retry
    src3 = (
        "def serve(conn, op):\n"
        "    while True:\n"
        "        try:\n"
        "            op()\n"
        "        except ValueError as e:\n"
        "            reply(conn, e)\n"
    )
    assert _ids(lint_source(src3, "fx.py")) == []


# -- MX306 un-barriered timing fixtures (ISSUE 5 satellite) -------------------

def test_fixture_mx306_unbarriered_delta():
    src = (
        "import time\n"
        "def bench(step, x):\n"
        "    t0 = time.time()\n"
        "    out = step(x)\n"
        "    return time.time() - t0\n"
    )
    findings = lint_source(src, "fx.py")
    assert _ids(findings) == ["MX306"]
    assert findings[0].line == 5
    # perf_counter, delta via a second stored read
    src2 = (
        "from time import perf_counter\n"
        "def bench(step, x):\n"
        "    t0 = perf_counter()\n"
        "    out = step(x)\n"
        "    t1 = perf_counter()\n"
        "    return t1 - t0\n"
    )
    assert _ids(lint_source(src2, "fx.py")) == ["MX306"]


def test_fixture_mx306_barriered_deltas_are_clean():
    # block_until_ready between start and read
    src = (
        "import time\n"
        "import jax\n"
        "def bench(step, x):\n"
        "    t0 = time.perf_counter()\n"
        "    out = step(x)\n"
        "    jax.block_until_ready(out)\n"
        "    return time.perf_counter() - t0\n"
    )
    assert _ids(lint_source(src, "fx.py")) == []
    # monotonic deadlines/backoff bookkeeping are not measurements
    src2 = (
        "import time\n"
        "def poll(op):\n"
        "    start = time.monotonic()\n"
        "    op()\n"
        "    return time.monotonic() - start\n"
    )
    assert _ids(lint_source(src2, "fx.py")) == []
    # no work between the reads: nothing is being mis-timed
    src3 = (
        "import time\n"
        "def stamp():\n"
        "    t0 = time.time()\n"
        "    return time.time() - t0\n"
    )
    assert _ids(lint_source(src3, "fx.py")) == []
    # blocking .result() (engine futures, precompile) counts as a barrier
    src4 = (
        "import time\n"
        "def bench(pool, job):\n"
        "    t0 = time.time()\n"
        "    fut = pool.submit(job)\n"
        "    fut.result()\n"
        "    return time.time() - t0\n"
    )
    assert _ids(lint_source(src4, "fx.py")) == []


def test_fixture_mx306_pragma_and_exempt_paths():
    src = (
        "import time\n"
        "def bench(step, x):\n"
        "    t0 = time.time()\n"
        "    out = step(x)\n"
        "    return time.time() - t0  # mxlint: disable=MX306\n"
    )
    assert _ids(lint_source(src, "fx.py")) == []
    src2 = src.replace("  # mxlint: disable=MX306", "")
    # the sanctioned timing homes are exempt wholesale
    assert _ids(lint_source(
        src2, "mxnet_tpu/telemetry/timeline.py")) == []
    assert _ids(lint_source(src2, "mxnet_tpu/utils/profiler.py")) == []


def test_tree_has_no_mx306_findings():
    """ISSUE 5 satellite: the tree self-lints clean of the un-barriered-
    timing footgun (every wall-clock measurement either blocks first or is
    explicitly pragma'd with its justification)."""
    from mxnet_tpu.analysis import lint_paths

    findings = [f for f in lint_paths([os.path.join(REPO, "mxnet_tpu")])
                if f.rule.id == "MX306"]
    assert not findings, "\n".join(f.format() for f in findings)


# -- MX309 host-sync-in-step-loop fixtures (ISSUE 9) ---------------------------

def test_fixture_mx309_host_sync_in_step_loop():
    src = (
        "import numpy as np\n"
        "def loop(batches, train_step, state):\n"
        "    for b in batches:\n"
        "        state = train_step(state, b)\n"
        "        loss = np.asarray(state[1])\n"
        "        acc = state[2].asnumpy()\n"
    )
    assert _ids(lint_source(src, "fx.py")) == ["MX309", "MX309"]


def test_fixture_mx309_scalar_pull_shapes():
    # float(name)/int(name): the classic per-step scalar pull
    src = (
        "def loop(batches, train_step, state, loss):\n"
        "    for b in batches:\n"
        "        state, loss = train_step(state, b)\n"
        "        print(float(loss))\n"
    )
    assert _ids(lint_source(src, "fx.py")) == ["MX309"]
    # attribute/subscript args are host metadata (shapes, pads): exempt
    src2 = (
        "def loop(batches, train_step, state):\n"
        "    for b in batches:\n"
        "        state = train_step(state, b)\n"
        "        n = int(b.shape[0])\n"
    )
    assert _ids(lint_source(src2, "fx.py")) == []


def test_fixture_mx309_only_fires_in_step_loops():
    # same syncs, no step dispatch in the loop: init/checkpoint loops may
    # pull freely
    src = (
        "import numpy as np\n"
        "def save_all(arrays):\n"
        "    out = []\n"
        "    for a in arrays:\n"
        "        out.append(np.asarray(a))\n"
        "    return out\n"
    )
    assert _ids(lint_source(src, "fx.py")) == []
    # a once-per-epoch pull AFTER the inner step loop is not blamed on it
    src2 = (
        "import numpy as np\n"
        "def fit(epochs, batches, train_step, state, gstate):\n"
        "    for e in range(epochs):\n"
        "        for b in batches:\n"
        "            state = train_step(state, b)\n"
        "        stats = np.asarray(gstate)\n"
    )
    assert _ids(lint_source(src2, "fx.py")) == []


def test_fixture_mx309_pragma_and_exemptions():
    src = (
        "import numpy as np\n"
        "def loop(batches, train_step, state):\n"
        "    for b in batches:\n"
        "        state = train_step(state, b)\n"
        "        loss = np.asarray(state[1])  # mxlint: disable=MX309\n"
    )
    assert _ids(lint_source(src, "fx.py")) == []
    src2 = src.replace("  # mxlint: disable=MX309", "")
    assert _ids(lint_source(src2, "fx.py")) == ["MX309"]
    # the telemetry/profiler timing homes are exempt wholesale
    assert _ids(lint_source(src2, "mxnet_tpu/telemetry/timeline.py")) == []
    assert _ids(lint_source(src2, "mxnet_tpu/utils/profiler.py")) == []


def test_tree_has_no_mx309_findings():
    """ISSUE 9: the tree self-lints clean of implicit host syncs in step
    loops — every intentional per-step pull (guard verdicts, host-metric
    paths, predict's output materialization) carries a justified pragma."""
    from mxnet_tpu.analysis import lint_paths

    findings = [f for f in lint_paths([os.path.join(REPO, "mxnet_tpu")])
                if f.rule.id == "MX309"]
    assert not findings, "\n".join(f.format() for f in findings)


# -- MX310 world-size-literal-in-closure fixtures (ISSUE 10) -------------------

def test_fixture_mx310_world_literal_in_closure():
    src = (
        "def build(mesh):\n"
        "    ndev = 8\n"
        "    def step(x):\n"
        "        return x / ndev\n"
        "    return step\n"
    )
    findings = lint_source(src, "fx.py")
    assert _ids(findings) == ["MX310"]
    assert findings[0].line == 4  # reported at the stale use
    # name matching covers the whole world-size vocabulary
    src2 = src.replace("ndev", "world_size")
    assert _ids(lint_source(src2, "fx.py")) == ["MX310"]


def test_fixture_mx310_healthy_idioms_clean():
    # derived from the live mesh: a call result, not a frozen literal
    src = (
        "def build(mesh):\n"
        "    ndev = int(mesh.shape['dp'])\n"
        "    def step(x):\n"
        "        return x / ndev\n"
        "    return step\n"
    )
    assert _ids(lint_source(src, "fx.py")) == []
    # passed as an argument: every (re)build sees the current world
    src2 = (
        "def build():\n"
        "    ndev = 8\n"
        "    def step(x, ndev):\n"
        "        return x / ndev\n"
        "    return step\n"
    )
    assert _ids(lint_source(src2, "fx.py")) == []
    # rebound inside the closure: not a capture
    src3 = (
        "def build():\n"
        "    ndev = 8\n"
        "    def step(x):\n"
        "        ndev = len(x)\n"
        "        return x / ndev\n"
        "    return step\n"
    )
    assert _ids(lint_source(src3, "fx.py")) == []
    # a literal used only in the binding scope is fine (no closure)
    src4 = (
        "def build():\n"
        "    ndev = 8\n"
        "    return list(range(ndev))\n"
    )
    assert _ids(lint_source(src4, "fx.py")) == []
    # the mesh/coordinator providers may define worlds from literals
    src5 = (
        "def build():\n"
        "    ndev = 8\n"
        "    def step(x):\n"
        "        return x / ndev\n"
        "    return step\n"
    )
    assert _ids(lint_source(src5, "mxnet_tpu/parallel/mesh.py")) == []
    assert _ids(lint_source(src5, "mxnet_tpu/resilience/elastic.py")) == []


def test_tree_has_no_mx310_findings():
    """ISSUE 10 satellite: the tree self-lints clean of world-size
    literals frozen into closures — every axis/world size a closure uses
    is derived from the live mesh/kvstore/coordinator or passed in."""
    from mxnet_tpu.analysis import lint_paths

    findings = [f for f in lint_paths([os.path.join(REPO, "mxnet_tpu")])
                if f.rule.id == "MX310"]
    assert not findings, "\n".join(f.format() for f in findings)


# -- MX311 fleet-actuation-outside-the-policy-loop fixtures (ISSUE 12) ---------

def test_fixture_mx311_direct_actuation():
    src = (
        "def rebalance(co, kv):\n"
        "    co.kill(3, reason='slow')\n"
        "    co.request_world(4)\n"
        "    kv.set_gradient_compression('int8')\n"
    )
    findings = lint_source(src, "mxnet_tpu/somewhere.py")
    assert _ids(findings) == ["MX311", "MX311", "MX311"]
    assert [f.line for f in findings] == [2, 3, 4]
    # coordinator-shaped receiver names all count for .kill
    src2 = (
        "def f(elastic_co, my_coordinator):\n"
        "    elastic_co.kill()\n"
        "    my_coordinator.kill(1)\n"
    )
    assert _ids(lint_source(src2, "mxnet_tpu/x.py")) == ["MX311", "MX311"]


def test_fixture_mx311_non_actuation_kills_clean():
    # os.kill / process handles are not fleet actuation; an override
    # delegating to its base class is a definition, not a site
    src = (
        "import os\n"
        "def f(proc):\n"
        "    os.kill(123, 9)\n"
        "    proc.kill()\n"
        "class S(Base):\n"
        "    def set_gradient_compression(self, c):\n"
        "        return super().set_gradient_compression(c)\n"
    )
    assert _ids(lint_source(src, "mxnet_tpu/x.py")) == []


def test_fixture_mx311_exemptions_and_pragma():
    src = "def f(co):\n    co.request_world(4)\n"
    # the policy loop and the lever's owner are the sanctioned homes
    assert _ids(lint_source(
        src, "mxnet_tpu/resilience/controller.py")) == []
    assert _ids(lint_source(src, "mxnet_tpu/resilience/elastic.py")) == []
    # tests and examples drive fleets by hand
    assert _ids(lint_source(src, "tests/test_x.py")) == []
    assert _ids(lint_source(src, "examples/distributed/demo.py")) == []
    # deliberate out-of-loop sites carry the audit-record pragma
    src_pr = ("def f(co):\n"
              "    co.request_world(4)  "
              "# mxlint: disable=MX311 - recovery runbook tool\n")
    assert _ids(lint_source(src_pr, "mxnet_tpu/x.py")) == []


def test_tree_has_no_mx311_findings():
    """ISSUE 12 satellite: fleet actuation in the tree flows through the
    FleetController policy loop — the two launch-config sites
    (fit/create_group applying a user's static compression spec) carry
    justified pragmas."""
    from mxnet_tpu.analysis import lint_paths

    findings = [f for f in lint_paths([os.path.join(REPO, "mxnet_tpu")])
                if f.rule.id == "MX311"]
    assert not findings, "\n".join(f.format() for f in findings)


# -- MX313 per-leaf-host-stat-loop fixtures (ISSUE 14) -------------------------

def test_fixture_mx313_per_leaf_stat_loop_in_traced_fn():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def step(params, grads):\n"
        "    stats = {}\n"
        "    for name, g in grads.items():\n"
        "        stats[name] = float(jnp.sum(jnp.abs(g)))\n"
        "    return stats\n"
    )
    findings = lint_source(src, "fx.py")
    assert _ids(findings) == ["MX313"]
    assert findings[0].line == 7  # reported at the materializing call
    # .item() / numpy shapes of the same pattern fire too (numpy also
    # trips the general traced-numpy rule MX201 — both are real)
    src2 = src.replace("float(jnp.sum(jnp.abs(g)))", "jnp.sum(g).item()")
    assert "MX313" in _ids(lint_source(src2, "fx.py"))


def test_fixture_mx313_clean_patterns():
    # a pure-jnp per-leaf loop (unrolled at trace) materializes nothing
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def step(params, grads):\n"
        "    stats = {}\n"
        "    for name, g in grads.items():\n"
        "        stats[name] = jnp.sum(jnp.abs(g))\n"
        "    return stats\n"
    )
    assert _ids(lint_source(src, "fx.py")) == []
    # the same loop OUTSIDE traced code is host-side tooling (the
    # sanctioned Monitor shape), not a traced-loop hazard
    src2 = (
        "def summarize(grads):\n"
        "    out = {}\n"
        "    for name, g in grads.items():\n"
        "        out[name] = float(abs(g).sum())\n"
        "    return out\n"
    )
    assert _ids(lint_source(src2, "fx.py")) == []
    # loops not over gradient-named values stay clean
    src3 = (
        "import jax\n"
        "@jax.jit\n"
        "def step(params, batches):\n"
        "    for b in batches:\n"
        "        x = float(b)\n"
        "    return x\n"
    )
    assert _ids(lint_source(src3, "fx.py")) == []


def test_fixture_mx313_pragma():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def step(grads):\n"
        "    out = []\n"
        "    for g in grads:\n"
        "        out.append(float(jnp.sum(g)))  "
        "# mxlint: disable=MX313 - debug tool\n"
        "    return out\n"
    )
    assert _ids(lint_source(src, "fx.py")) == []


def test_tree_has_no_mx313_findings():
    """ISSUE 14 satellite: the tree self-lints clean — per-layer stats
    come from the in-graph health engine, not per-leaf host pulls."""
    from mxnet_tpu.analysis import lint_paths

    findings = [f for f in lint_paths([os.path.join(REPO, "mxnet_tpu")])
                if f.rule.id == "MX313"]
    assert not findings, "\n".join(f.format() for f in findings)


# -- MX307 leaked-span fixtures (ISSUE 6 satellite) ----------------------------

def test_fixture_mx307_leaked_span():
    src = (
        "def loop(tl, batches):\n"
        "    for i, b in enumerate(batches):\n"
        "        span = tl.begin_step(0, i)\n"
        "        span.mark('device')\n"
        "        step(b)\n"
    )
    findings = lint_source(src, "fx.py")
    assert _ids(findings) == ["MX307"]
    assert findings[0].line == 3


def test_fixture_mx307_bare_calls():
    # a discarded begin_step can never be ended
    src = (
        "def loop(tl):\n"
        "    tl.begin_step(0, 0)\n"
    )
    assert _ids(lint_source(src, "fx.py")) == ["MX307"]
    # phase()/timed() return context managers; a bare call records nothing
    src2 = (
        "from mxnet_tpu import telemetry\n"
        "def push(kv, grads):\n"
        "    telemetry.phase('kvstore_push')\n"
        "    kv.push_many(grads)\n"
    )
    assert _ids(lint_source(src2, "fx.py")) == ["MX307"]
    src3 = (
        "from mxnet_tpu.telemetry import timed\n"
        "def stage(x):\n"
        "    timed('stage')\n"
        "    return work(x)\n"
    )
    assert _ids(lint_source(src3, "fx.py")) == ["MX307"]


def test_fixture_mx307_clean_patterns():
    # context-manager span: __exit__ closes it
    src = (
        "def loop(tl, batches):\n"
        "    for i, b in enumerate(batches):\n"
        "        with tl.begin_step(0, i) as span:\n"
        "            span.mark('device')\n"
        "            step(b)\n"
    )
    assert _ids(lint_source(src, "fx.py")) == []
    # explicit .end() anywhere in the function (incl. a finally)
    src2 = (
        "def loop(tl, batches):\n"
        "    for i, b in enumerate(batches):\n"
        "        span = tl.begin_step(0, i)\n"
        "        try:\n"
        "            step(b)\n"
        "        finally:\n"
        "            span.end()\n"
    )
    assert _ids(lint_source(src2, "fx.py")) == []
    # the fit-loop shape: conditional open, conditional end
    src3 = (
        "def loop(tl, batches):\n"
        "    for i, b in enumerate(batches):\n"
        "        span = tl.begin_step(0, i) if tl is not None else None\n"
        "        step(b)\n"
        "        if span is not None:\n"
        "            span.end()\n"
    )
    assert _ids(lint_source(src3, "fx.py")) == []
    # with-entered phase is the sanctioned use
    src4 = (
        "from mxnet_tpu import telemetry\n"
        "def push(kv, grads):\n"
        "    with telemetry.phase('kvstore_push'):\n"
        "        kv.push_many(grads)\n"
    )
    assert _ids(lint_source(src4, "fx.py")) == []


def test_fixture_mx307_pragma_and_exempt_paths():
    src = (
        "def loop(tl):\n"
        "    span = tl.begin_step(0, 0)  # mxlint: disable=MX307\n"
        "    step()\n"
    )
    assert _ids(lint_source(src, "fx.py")) == []
    src2 = src.replace("  # mxlint: disable=MX307", "")
    # the primitives' home is exempt wholesale
    assert _ids(lint_source(
        src2, "mxnet_tpu/telemetry/timeline.py")) == []


def test_tree_has_no_mx307_findings():
    """ISSUE 6 satellite: the tree self-lints clean of leaked spans —
    every begin_step is closed on every path and every phase()/timed()
    is with-entered."""
    from mxnet_tpu.analysis import lint_paths

    findings = [f for f in lint_paths([os.path.join(REPO, "mxnet_tpu")])
                if f.rule.id == "MX307"]
    assert not findings, "\n".join(f.format() for f in findings)


# -- MX312 pallas-kernel-discipline fixtures (ISSUE 13) ------------------------

def test_fixture_mx312_pallas_call_outside_layer():
    src = (
        "from jax.experimental import pallas as pl\n"
        "def hot(x):\n"
        "    return pl.pallas_call(k, out_shape=o)(x)\n"
        "def hotter(x):\n"
        "    return pl.pallas_call(k2, out_shape=o)(x)\n"
    )
    findings = lint_source(src, "mxnet_tpu/models/fastnet.py")
    assert [f.rule.id for f in findings] == ["MX312", "MX312"]
    assert [f.line for f in findings] == [3, 5]


def test_fixture_mx312_kernel_module_missing_registry_entry():
    # inside the layer but unpriced: ONE finding per module, at the
    # first pallas_call
    src = (
        "from jax.experimental import pallas as pl\n"
        "def my_kernel(x):\n"
        "    return pl.pallas_call(k, out_shape=o, name='my_kernel')(x)\n"
        "def my_kernel2(x):\n"
        "    return pl.pallas_call(k2, out_shape=o, name='my_kernel2')(x)\n"
    )
    findings = lint_source(src, "mxnet_tpu/ops/pallas/newkern.py")
    assert [f.rule.id for f in findings] == ["MX312"]
    assert findings[0].line == 3
    assert "register" in findings[0].message


def test_fixture_mx312_registered_kernel_module_clean():
    src = (
        "from jax.experimental import pallas as pl\n"
        "from .registry import register_kernel\n"
        "def my_kernel(x):\n"
        "    return pl.pallas_call(k, out_shape=o, name='my_kernel')(x)\n"
        "register_kernel('my_kernel', cost_fn)\n"
    )
    assert [f.rule.id for f in
            lint_source(src, "mxnet_tpu/ops/pallas/newkern.py")] == []
    # modules that never emit a pallas_call owe the registry nothing
    assert lint_source("def f(x):\n    return x\n",
                       "mxnet_tpu/ops/pallas/helpers.py") == []


def test_fixture_mx312_pragma_escape_hatch():
    src = (
        "from jax.experimental import pallas as pl\n"
        "def hot(x):\n"
        "    return pl.pallas_call(k, out_shape=o)(x)"
        "  # mxlint: disable=MX312 - vendored prototype\n"
    )
    assert [f.rule.id for f in
            lint_source(src, "mxnet_tpu/models/fastnet.py")] == []


def test_self_lint_mx312_clean():
    """The kernel layer itself passes its own discipline: every module
    emitting a pallas_call registers a cost model, and no pallas_call
    lives outside ops/pallas/."""
    from mxnet_tpu.analysis.source_lint import lint_paths

    findings = [f for f in lint_paths([os.path.join(REPO, "mxnet_tpu")])
                if f.rule.id == "MX312"]
    assert not findings, "\n".join(f.format() for f in findings)


# -- MX314 raw-profiler-capture fixtures (ISSUE 15) ----------------------------

def test_fixture_mx314_raw_jax_profiler_capture():
    # a raw jax.profiler capture outside utils/profiler.py /
    # telemetry/profiling.py: both the start and the raw stop fire
    src = (
        "import jax\n"
        "def cap(d):\n"
        "    jax.profiler.start_trace(d)\n"
        "    run()\n"
        "    jax.profiler.stop_trace()\n"
    )
    findings = lint_source(src, "mxnet_tpu/models/fastnet.py")
    assert [f.rule.id for f in findings] == ["MX314", "MX314"]
    assert [f.line for f in findings] == [3, 5]
    # the context-manager shape fires too, and so does a name bound by
    # `from jax import profiler`
    src2 = (
        "import jax\n"
        "def cap(d):\n"
        "    with jax.profiler.trace(d):\n"
        "        run()\n"
    )
    assert [f.rule.id for f in
            lint_source(src2, "mxnet_tpu/models/fastnet.py")] == ["MX314"]
    src3 = (
        "from jax import profiler\n"
        "def cap(d):\n"
        "    profiler.start_trace(d)\n"
    )
    assert "MX314" in [f.rule.id for f in
                       lint_source(src3, "mxnet_tpu/models/fastnet.py")]


def test_fixture_mx314_unguarded_start_trace():
    # even the sanctioned wrapper fires when its stop is not in a
    # finally: an exception leaks the process-global running trace
    src = (
        "from mxnet_tpu.utils import profiler\n"
        "def cap(d):\n"
        "    profiler.start_trace(d)\n"
        "    run()\n"
        "    profiler.stop_trace()\n"
    )
    findings = lint_source(src, "mxnet_tpu/models/fastnet.py")
    assert [f.rule.id for f in findings] == ["MX314"]
    assert findings[0].line == 3
    assert "finally" in findings[0].message
    # the low-level capture API leaks identically and fires identically
    src2 = (
        "from mxnet_tpu.telemetry import profiling\n"
        "def cap(d):\n"
        "    profiling.start_capture(d)\n"
        "    run()\n"
        "    profiling.stop_capture()\n"
    )
    findings = lint_source(src2, "mxnet_tpu/models/fastnet.py")
    assert [f.rule.id for f in findings] == ["MX314"]
    assert "start_capture" in findings[0].message
    # a nested def inside a try body owns ITS start: the outer finally
    # cannot guard a deferred body that runs after the finally fired
    src3 = (
        "from mxnet_tpu.utils import profiler\n"
        "def f(d):\n"
        "    try:\n"
        "        def helper():\n"
        "            profiler.start_trace(d)\n"
        "        register(helper)\n"
        "    finally:\n"
        "        profiler.stop_trace()\n"
    )
    findings = lint_source(src3, "mxnet_tpu/models/fastnet.py")
    assert [f.line for f in findings] == [5], findings


def test_fixture_mx314_guarded_and_capture_clean():
    # finally-guarded stop: clean
    src = (
        "from mxnet_tpu.utils import profiler\n"
        "def cap(d):\n"
        "    profiler.start_trace(d)\n"
        "    try:\n"
        "        run()\n"
        "    finally:\n"
        "        profiler.stop_trace()\n"
    )
    assert lint_source(src, "mxnet_tpu/models/fastnet.py") == []
    # the sanctioned capture() context manager: clean
    src2 = (
        "from mxnet_tpu.telemetry import profiling\n"
        "def cap(d):\n"
        "    with profiling.capture(d):\n"
        "        run()\n"
    )
    assert lint_source(src2, "mxnet_tpu/models/fastnet.py") == []
    # a second function's finally does NOT excuse this one's bare start
    src3 = (
        "from mxnet_tpu.utils import profiler\n"
        "def bare(d):\n"
        "    profiler.start_trace(d)\n"
        "def guarded(d):\n"
        "    profiler.start_trace(d)\n"
        "    try:\n"
        "        run()\n"
        "    finally:\n"
        "        profiler.stop_trace()\n"
    )
    findings = lint_source(src3, "mxnet_tpu/models/fastnet.py")
    assert [f.line for f in findings] == [3]


def test_fixture_mx314_pragma_and_owner_exemptions():
    src = (
        "import jax\n"
        "def cap(d):\n"
        "    jax.profiler.start_trace(d)"
        "  # mxlint: disable=MX314 - raw capture for the xprof UI\n"
    )
    assert lint_source(src, "mxnet_tpu/models/fastnet.py") == []
    # the owner modules ARE the sanctioned doorway
    raw = (
        "import jax\n"
        "def start_capture(d):\n"
        "    jax.profiler.start_trace(d)\n"
    )
    assert lint_source(raw, "mxnet_tpu/telemetry/profiling.py") == []
    assert lint_source(raw, "mxnet_tpu/utils/profiler.py") == []


def test_self_lint_mx314_clean():
    """No raw jax.profiler captures outside the profiling layer, and no
    unguarded start_trace anywhere in the tree."""
    from mxnet_tpu.analysis.source_lint import lint_paths

    findings = [f for f in lint_paths([os.path.join(REPO, "mxnet_tpu")])
                if f.rule.id == "MX314"]
    assert not findings, "\n".join(f.format() for f in findings)


# -- MX315 checkpoint-discipline fixtures (ISSUE 17 satellite) -----------------

def test_fixture_mx315_direct_save_sharded():
    # a direct durable write outside the checkpoint plane: races the
    # async writer's `.tmp.<step>` staging, dodges retention GC and the
    # `checkpoint` badput pricing
    src = (
        "from mxnet_tpu.utils import checkpoint as ck\n"
        "def snapshot(d, step, params):\n"
        "    ck.save_sharded(d, step, params)\n"
    )
    findings = lint_source(src, "mxnet_tpu/models/fastnet.py")
    assert [f.rule.id for f in findings] == ["MX315"]
    assert "durability ordering" in findings[0].message

    # the private staging helpers are just as off-limits
    src2 = (
        "from mxnet_tpu.utils.checkpoint import _write_manifest\n"
        "def stage(d, shards):\n"
        "    _write_manifest(d, shards)\n"
    )
    assert [f.rule.id for f in
            lint_source(src2, "mxnet_tpu/models/fastnet.py")] == ["MX315"]


def test_fixture_mx315_reads_and_sanctioned_paths_clean():
    # loads / latest_step / the ckpt_async doorway never match
    src = (
        "from mxnet_tpu.utils import checkpoint as ck\n"
        "from mxnet_tpu.resilience import ckpt_async\n"
        "def resume(d, w):\n"
        "    step = ck.latest_step(d)\n"
        "    state = ck.load_sharded(d, step)\n"
        "    ckpt_async.save_now(d, step, state[0], symbol=None)\n"
        "    w.submit(None)\n"
        "    return state\n"
    )
    assert lint_source(src, "mxnet_tpu/models/fastnet.py") == []


def test_fixture_mx315_pragma_and_owner_exemptions():
    src = (
        "from mxnet_tpu.utils import checkpoint as ck\n"
        "def snapshot(d, step, params):\n"
        "    ck.save_sharded(d, step, params)"
        "  # mxlint: disable=MX315 - migration shim, bypasses GC on purpose\n"
    )
    assert lint_source(src, "mxnet_tpu/models/fastnet.py") == []
    # the owner modules ARE the checkpoint plane
    raw = (
        "def save_now(d, step, params):\n"
        "    return save_sharded(d, step, params)\n"
    )
    assert lint_source(raw, "mxnet_tpu/utils/checkpoint.py") == []
    assert lint_source(raw, "mxnet_tpu/resilience/ckpt_async.py") == []
    # tests drive save_sharded directly all over — exempt
    assert lint_source(raw, "tests/test_sharded_checkpoint.py") == []


def test_self_lint_mx315_clean():
    """Every durable checkpoint write in the tree flows through the
    checkpoint plane (utils/checkpoint.py + resilience/ckpt_async.py)."""
    from mxnet_tpu.analysis.source_lint import lint_paths

    findings = [f for f in lint_paths([os.path.join(REPO, "mxnet_tpu")])
                if f.rule.id == "MX315"]
    assert not findings, "\n".join(f.format() for f in findings)


# -- MX316 run-ledger-discipline fixtures (ISSUE 20 satellite) -----------------

def test_fixture_mx316_env_consultation_and_summary_emit():
    # a layer resolving the ledger dir itself to write its own summary
    # file: un-CRC'd records the trend/compare gates cannot read, plus a
    # duplicate run_summary event skewing the golden-key stream
    src = (
        "import os, json\n"
        "def summarize(hub, outcomes):\n"
        "    d = os.environ.get('MXNET_TPU_LEDGER_DIR')\n"
        "    with open(os.path.join(d, 'summary.json'), 'w') as f:\n"
        "        json.dump(outcomes, f)\n"
        "    hub.emit('run_summary', run_id='abc')\n"
    )
    findings = lint_source(src, "mxnet_tpu/models/fastnet.py")
    assert [f.rule.id for f in findings] == ["MX316", "MX316"]
    assert "ledger_dir()" in findings[0].message
    assert "run_summary" in findings[1].message

    # writing the env var directly is the same bypass
    src2 = (
        "import os\n"
        "def redirect(d):\n"
        "    os.environ['MXNET_TPU_LEDGER_DIR'] = d\n"
    )
    assert [f.rule.id for f in
            lint_source(src2, "mxnet_tpu/models/fastnet.py")] == ["MX316"]


def test_fixture_mx316_sanctioned_paths_clean():
    # the sanctioned shapes: ledger_dir()/record_run, other
    # env vars, other emit kinds — and monkeypatch.setenv (keyword "key"
    # position is not the getter-call shape MX316 matches)
    src = (
        "import os\n"
        "def ok(hub, monkeypatch):\n"
        "    from mxnet_tpu.telemetry import ledger\n"
        "    monkeypatch.setenv('MXNET_TPU_LEDGER_DIR', '/tmp/x')\n"
        "    d = ledger.ledger_dir()\n"
        "    ledger.record_run('fit', fingerprint='fp')\n"
        "    flight = os.environ.get('MXNET_TPU_FLIGHT_DIR')\n"
        "    hub.emit('epoch_summary', mfu_pct=1.0)\n"
    )
    assert lint_source(src, "mxnet_tpu/models/fastnet.py") == []


def test_fixture_mx316_pragma_and_owner_exemptions():
    src = (
        "import os\n"
        "def probe(hub):\n"
        "    d = os.environ.get('MXNET_TPU_LEDGER_DIR')"
        "  # mxlint: disable=MX316 - launcher probe, read-only\n"
    )
    assert lint_source(src, "mxnet_tpu/models/fastnet.py") == []
    # the owner module IS the ledger
    raw = (
        "import os\n"
        "def ledger_dir():\n"
        "    return os.environ.get('MXNET_TPU_LEDGER_DIR') or None\n"
        "def announce(hub, rec):\n"
        "    hub.emit('run_summary', run_id=rec['run_id'])\n"
    )
    assert lint_source(raw, "mxnet_tpu/telemetry/ledger.py") == []
    # tests point the store at tmpdirs constantly — exempt
    assert lint_source(raw, "tests/test_ledger.py") == []


def test_self_lint_mx316_clean():
    """Every run-summary write in the tree flows through
    telemetry/ledger.py (the one writer the gates can read)."""
    from mxnet_tpu.analysis.source_lint import lint_paths

    findings = [f for f in lint_paths([os.path.join(REPO, "mxnet_tpu")])
                if f.rule.id == "MX316"]
    assert not findings, "\n".join(f.format() for f in findings)


# -- MX308 unpinned-wire-collective fixtures (ISSUE 7 satellite) ---------------

def test_fixture_mx308_unpinned_collective():
    # a wire collective in comm/ with no optimization_barrier anywhere:
    # XLA can commute the encode/decode casts across it (fp32 on the
    # wire, compression silently lost — allreduce.py's documented bug
    # class)
    src = (
        "import jax.lax as lax\n"
        "def exchange(q, axis):\n"
        "    s = lax.all_to_all(q, axis, 0, 0)\n"
        "    return lax.all_gather(s, axis)\n"
    )
    findings = lint_source(src, "mxnet_tpu/comm/fx.py")
    assert _ids(findings) == ["MX308", "MX308"]
    assert sorted(f.line for f in findings) == [3, 4]
    # pinned on one side only is still flagged (the convert commutes
    # across whichever side is open)
    src2 = (
        "import jax.lax as lax\n"
        "def exchange(q, axis):\n"
        "    (q,) = lax.optimization_barrier((q,))\n"
        "    return lax.all_to_all(q, axis, 0, 0)\n"
    )
    assert _ids(lint_source(src2, "mxnet_tpu/comm/fx.py")) == ["MX308"]


def test_fixture_mx308_pinned_and_out_of_scope():
    # barriers lexically before AND after the collective: clean
    src = (
        "import jax.lax as lax\n"
        "def exchange(q, axis):\n"
        "    (q,) = lax.optimization_barrier((q,))\n"
        "    s = lax.all_to_all(q, axis, 0, 0)\n"
        "    g = lax.all_gather(s, axis)\n"
        "    (g,) = lax.optimization_barrier((g,))\n"
        "    return g\n"
    )
    assert _ids(lint_source(src, "mxnet_tpu/comm/fx.py")) == []
    # the rule is scoped to comm/: collectives elsewhere are not its
    # business (MX304 polices raw grad psums outside comm/)
    src2 = (
        "import jax.lax as lax\n"
        "def gather(q, axis):\n"
        "    return lax.all_gather(q, axis)\n"
    )
    assert _ids(lint_source(src2, "mxnet_tpu/parallel/fx.py")) == []
    # nested defs are their own scope: an inner pinned exchange does not
    # excuse an outer bare one
    src3 = (
        "import jax.lax as lax\n"
        "def outer(q, axis):\n"
        "    def inner(v):\n"
        "        (v,) = lax.optimization_barrier((v,))\n"
        "        v = lax.all_to_all(v, axis, 0, 0)\n"
        "        (v,) = lax.optimization_barrier((v,))\n"
        "        return v\n"
        "    return lax.all_gather(inner(q), axis)\n"
    )
    assert _ids(lint_source(src3, "mxnet_tpu/comm/fx.py")) == ["MX308"]


def test_fixture_mx308_lambda_and_module_scopes():
    # a lambda body is its own scope: an unpinned collective in one
    # cannot hide behind barriers in the enclosing function
    src = (
        "import jax.lax as lax\n"
        "def exchange(q, axis):\n"
        "    (q,) = lax.optimization_barrier((q,))\n"
        "    f = lambda v: lax.all_gather(v, axis)\n"
        "    (q,) = lax.optimization_barrier((q,))\n"
        "    return f(q)\n"
    )
    findings = lint_source(src, "mxnet_tpu/comm/fx.py")
    assert _ids(findings) == ["MX308"]
    assert findings[0].line == 4
    # module-level collectives are scanned too
    src2 = (
        "import jax.lax as lax\n"
        "OUT = lax.all_to_all(IN, 'dp', 0, 0)\n"
    )
    assert _ids(lint_source(src2, "mxnet_tpu/comm/fx.py")) == ["MX308"]


def test_fixture_mx308_pragma_suppression():
    src = (
        "import jax.lax as lax\n"
        "def exchange(q, axis):\n"
        "    return lax.all_to_all(q, axis, 0, 0)"
        "  # mxlint: disable=MX308\n"
    )
    assert _ids(lint_source(src, "mxnet_tpu/comm/fx.py")) == []
    src2 = src.replace("  # mxlint: disable=MX308", "")
    assert _ids(lint_source(src2, "mxnet_tpu/comm/fx.py")) == ["MX308"]


def test_tree_has_no_mx308_findings():
    """ISSUE 7 satellite: the tree self-lints clean — every wire
    collective in comm/ (fused AND per-bucket paths) is barrier-pinned
    on both sides."""
    from mxnet_tpu.analysis import lint_paths

    findings = [f for f in lint_paths([os.path.join(REPO, "mxnet_tpu")])
                if f.rule.id == "MX308"]
    assert not findings, "\n".join(f.format() for f in findings)


# -- Pass 2: graph verifier fixtures ------------------------------------------

def test_fixture_duplicate_argument():
    g = mx.sym.Variable("x") + mx.sym.Variable("x")
    findings = [f for f in verify_symbol(g, {"x": (2, 2)}) if f.is_error]
    assert _ids(findings) == ["MX401"]
    with pytest.raises(MXNetError, match="MX401"):
        g.verify(arg_shapes={"x": (2, 2)})


def test_fixture_shape_conflict():
    data = mx.sym.Variable("data")
    fc = mx.symbol.FullyConnected(data=data, num_hidden=3, name="fc1")
    bad = fc + data  # (4,3) + (4,5)
    findings = [f for f in verify_symbol(bad, {"data": (4, 5)})
                if f.is_error]
    assert _ids(findings) == ["MX402"]
    msg = findings[0].message
    assert "_Plus" in msg and "input chain" in msg  # op + chain named
    with pytest.raises(MXNetError, match="MX402"):
        bad.verify(arg_shapes={"data": (4, 5)})


def test_fixture_dtype_conflict():
    lhs = mx.sym.Variable("l", shape=(2, 2), dtype=np.float32)
    rhs = mx.sym.Variable("r", shape=(2, 2), dtype=np.float16)
    with pytest.raises(MXNetError, match="MX403"):
        (lhs + rhs).verify()


def test_embedding_mixed_dtypes_allowed():
    """Embedding is heterogeneous by design: int ids + float table."""
    emb = mx.symbol.Embedding(data=mx.sym.Variable("tokens"),
                              input_dim=16, output_dim=4, name="emb")
    findings = emb.verify(arg_shapes={"tokens": (2, 8)},
                          arg_dtypes={"tokens": np.int32,
                                      "emb_weight": np.float32})
    assert not [f for f in findings if f.is_error]


def test_unused_output_warning():
    split = mx.symbol.SliceChannel(mx.sym.Variable("data"), num_outputs=2,
                                   name="split")
    one_head = split[0]  # output 1 computed, never consumed
    findings = one_head.verify(arg_shapes={"data": (4, 6)})
    assert "MX404" in _ids(findings)
    assert not [f for f in findings if f.is_error]  # warning only


def test_unreachable_node_in_json():
    net = mx.symbol.FullyConnected(data=mx.sym.Variable("data"),
                                   num_hidden=3, name="fc1")
    import json

    graph = json.loads(net.tojson())
    graph["nodes"].append({"op": "null", "name": "orphan", "inputs": []})
    findings = verify_json(json.dumps(graph))
    assert "MX405" in _ids(findings)


def test_verify_runs_on_bind():
    """Acceptance: bind invokes verify automatically and names the node."""
    import mxnet_tpu.ndarray as nd

    net = mx.symbol.FullyConnected(data=mx.sym.Variable("data"),
                                   num_hidden=3, name="fc1")
    args = {"data": nd.zeros((4, 5)), "fc1_weight": nd.zeros((3, 9)),
            "fc1_bias": nd.zeros((3,))}
    with pytest.raises(MXNetError) as ei:
        net.bind(mx.cpu(), args)
    assert "fc1" in str(ei.value) and "MX402" in str(ei.value)
    # the env gate turns it off (failure then happens later, at trace)
    os.environ["MXNET_TPU_VERIFY"] = "0"
    try:
        net.bind(mx.cpu(), args)  # bind itself now succeeds
    finally:
        del os.environ["MXNET_TPU_VERIFY"]


# -- Pass 3: jaxpr audit ------------------------------------------------------

def test_jaxpr_audit_costs_and_promotion():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.analysis import audit_executor, audit_jaxpr

    net = mx.symbol.FullyConnected(data=mx.sym.Variable("data"),
                                   num_hidden=8, name="fc1")
    exe = net.simple_bind(mx.cpu(), data=(16, 32))
    rep = audit_executor(exe)
    assert not rep.errors
    by_prim = {r["primitive"]: r for r in rep.rows}
    # FC = x@W.T + b: 2*M*N*K MACs-as-flops
    assert by_prim["dot_general"]["flops"] == 2 * 16 * 32 * 8
    assert rep.totals["bytes"] > 0

    def leaky(x):
        return x.astype(jnp.float32) * 2.0

    closed = jax.make_jaxpr(leaky)(jnp.ones((4, 4), jnp.bfloat16))
    rep2 = audit_jaxpr(closed, intended_dtype=jnp.bfloat16)
    assert "MX502" in [f.rule.id for f in rep2.findings]


# -- MX70x: concurrency pass (ISSUE 11) ---------------------------------------

def _cc_ids(src):
    from mxnet_tpu.analysis import concurrency

    return [f.rule.id for f in concurrency.lint_source(src, "fx.py")]


def test_fixture_mx701_unlocked_shared_attr():
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.count = 0\n"
        "        self._t = threading.Thread(target=self._work,\n"
        "                                   daemon=True)\n"
        "    def _work(self):\n"
        "        self.count += 1\n"
        "    def bump(self):\n"
        "        self.count += 1\n"
    )
    findings = [f for f in _cc_ids(src)]
    assert findings == ["MX701"]


def test_fixture_mx701_common_lock_is_clean():
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.count = 0\n"
        "        self._t = threading.Thread(target=self._work,\n"
        "                                   daemon=True)\n"
        "    def _work(self):\n"
        "        with self._lock:\n"
        "            self.count += 1\n"
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self.count += 1\n"
    )
    assert _cc_ids(src) == []


def test_fixture_mx701_weakref_callback_and_container_mutator():
    """GC-callback entry point + .append() mutator (the ledger shape)."""
    src = (
        "import threading\n"
        "import weakref\n"
        "class Ledger:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.rows = []\n"
        "    def add(self, arr):\n"
        "        ref = weakref.ref(arr, self._on_dead)\n"
        "        self.rows.append(ref)\n"
        "    def _on_dead(self, ref):\n"
        "        self.rows.remove(ref)\n"
    )
    assert _cc_ids(src) == ["MX701"]


def test_fixture_mx701_private_helper_under_lock_is_clean():
    """The guaranteed-held-lock inference: a private helper whose every
    call site holds the lock needs no pragma."""
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.n = 0\n"
        "        self._t = threading.Thread(target=self._work,\n"
        "                                   daemon=True)\n"
        "    def _bump_locked(self):\n"
        "        self.n += 1\n"
        "    def _work(self):\n"
        "        with self._lock:\n"
        "            self._bump_locked()\n"
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self._bump_locked()\n"
    )
    assert _cc_ids(src) == []


def test_fixture_mx702_lock_order_inversion():
    src = (
        "import threading\n"
        "A = threading.Lock()\n"
        "B = threading.Lock()\n"
        "def f():\n"
        "    with A:\n"
        "        with B:\n"
        "            pass\n"
        "def g():\n"
        "    with B:\n"
        "        with A:\n"
        "            pass\n"
    )
    assert _cc_ids(src) == ["MX702"]


def test_fixture_mx702_consistent_order_is_clean():
    src = (
        "import threading\n"
        "A = threading.Lock()\n"
        "B = threading.Lock()\n"
        "def f():\n"
        "    with A:\n"
        "        with B:\n"
        "            pass\n"
        "def g():\n"
        "    with A:\n"
        "        with B:\n"
        "            pass\n"
    )
    assert _cc_ids(src) == []


def test_fixture_mx702_via_call_hop():
    """The one-hop edge: holding A while calling a method that takes B,
    against a method taking them in the other order."""
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def _take_b(self):\n"
        "        with self._b:\n"
        "            pass\n"
        "    def f(self):\n"
        "        with self._a:\n"
        "            self._take_b()\n"
        "    def g(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n"
    )
    assert "MX702" in _cc_ids(src)


def test_fixture_mx703_bare_wait():
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self.lock = threading.Lock()\n"
        "        self.cv = threading.Condition(self.lock)\n"
        "    def bad(self):\n"
        "        with self.cv:\n"
        "            self.cv.wait()\n"
    )
    assert _cc_ids(src) == ["MX703"]


def test_fixture_mx703_wait_for_and_loop_are_clean():
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self.lock = threading.Lock()\n"
        "        self.cv = threading.Condition(self.lock)\n"
        "        self.ready = False\n"
        "    def ok1(self):\n"
        "        with self.cv:\n"
        "            self.cv.wait_for(lambda: self.ready)\n"
        "    def ok2(self):\n"
        "        with self.cv:\n"
        "            while not self.ready:\n"
        "                self.cv.wait()\n"
    )
    assert _cc_ids(src) == []


def test_fixture_mx704_unjoined_non_daemon_thread():
    src = (
        "import threading\n"
        "def spawn():\n"
        "    t = threading.Thread(target=print)\n"
        "    t.start()\n"
    )
    assert _cc_ids(src) == ["MX704"]


def test_fixture_mx704_daemon_or_joined_is_clean():
    src = (
        "import threading\n"
        "def ok1():\n"
        "    threading.Thread(target=print, daemon=True).start()\n"
        "def ok2():\n"
        "    t = threading.Thread(target=print)\n"
        "    t.start()\n"
        "    t.join()\n"
        "class C:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=print)\n"
        "        self._t.start()\n"
        "    def stop(self):\n"
        "        self._t.join()\n"
    )
    assert _cc_ids(src) == []


def test_fixture_mx705_fresh_lock():
    """The real-world citation: comm/stats.py:161 (pre-fix) locked
    `getattr(self, '_lock', threading.Lock())` — a fresh private lock
    whenever _lock was missing, guarding nothing."""
    src = (
        "import threading\n"
        "class R:\n"
        "    def reset(self):\n"
        "        with getattr(self, '_lock', threading.Lock()):\n"
        "            self.x = 1\n"
        "def direct():\n"
        "    with threading.Lock():\n"
        "        pass\n"
    )
    ids = _cc_ids(src)
    assert ids == ["MX705", "MX705"]


def test_fixture_mx705_reused_lock_is_clean():
    src = (
        "import threading\n"
        "class R:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def reset(self):\n"
        "        with self._lock:\n"
        "            self.x = 1\n"
    )
    assert _cc_ids(src) == []


def test_fixture_mx70x_pragma_suppression():
    src = (
        "import threading\n"
        "def spawn():\n"
        "    t = threading.Thread(target=print)  "
        "# mxlint: disable=MX704 - joined by the caller\n"
        "    t.start()\n"
    )
    assert _cc_ids(src) == []


def test_concurrency_lockwatch_factory_counts_as_lock_ctor():
    """Locks built by the analysis.lockwatch factory are first-class in
    the static model: same rules, same aliasing."""
    src = (
        "from mxnet_tpu.analysis.lockwatch import named_condition, "
        "named_lock\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self.lock = named_lock('s')\n"
        "        self.cv = named_condition('s.cv', self.lock)\n"
        "    def bad(self):\n"
        "        with self.cv:\n"
        "            self.cv.wait()\n"
    )
    assert _cc_ids(src) == ["MX703"]


def test_self_lint_concurrency_clean():
    """ISSUE 11 gate: the tree self-lints MX701-MX705 clean (fixed or
    pragma'd with a justification)."""
    from mxnet_tpu.analysis import concurrency

    findings = [f for f in concurrency.lint_paths(
        [os.path.join(REPO, "mxnet_tpu")])
        if f.rule.id.startswith("MX70")]
    assert not findings, "\n".join(f.format() for f in findings)


def test_cli_concurrency_flag(tmp_path):
    """`python -m mxnet_tpu.analysis --concurrency` reports MX70x."""
    bad = tmp_path / "seeded.py"
    bad.write_text(
        "import threading\n"
        "def spawn():\n"
        "    t = threading.Thread(target=print)\n"
        "    t.start()\n")
    proc = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.analysis", "--concurrency",
         str(bad)],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr  # warning-grade
    assert "MX704" in proc.stdout
    # and --warnings-as-errors promotes it to a failing exit
    proc = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.analysis", "--concurrency",
         "--warnings-as-errors", str(bad)],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=240)
    assert proc.returncode == 1


# -- the self-lint gate -------------------------------------------------------

def test_self_lint_package_clean():
    """mxlint over mxnet_tpu/ itself: zero errors (warnings allowed)."""
    from mxnet_tpu.analysis import lint_paths

    findings = lint_paths([os.path.join(REPO, "mxnet_tpu")])
    errors = [f for f in findings if f.is_error]
    assert not errors, "\n".join(f.format() for f in errors)


@pytest.mark.parametrize("target,expect_ok", [
    (os.path.join(REPO, "mxnet_tpu"), True),
    (None, False),  # seeded violation file, built in the test
])
def test_cli_exit_codes(tmp_path, target, expect_ok):
    """Acceptance: `python -m mxnet_tpu.analysis mxnet_tpu/` exits 0; a
    seeded violation makes it exit non-zero with the rule id printed."""
    if target is None:
        bad = tmp_path / "seeded.py"
        bad.write_text("from jax import shard_map\n")
        target = str(bad)
    proc = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.analysis", target],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=240)
    if expect_ok:
        assert proc.returncode == 0, proc.stdout + proc.stderr
    else:
        assert proc.returncode == 1
        assert "MX101" in proc.stdout
