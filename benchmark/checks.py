"""What decides ``correct``, apart from the epoch readings: where the step
ran, that replicas agree, and that the served precision is the stated one.
The spy and the placement checks are copies of ``chip_smoke.py``'s.
"""

from __future__ import annotations

import os

import numpy as np

import catalog


class StepSpy:
    """Wraps the train-step callable ``fit`` builds, without changing what
    it dispatches: counts calls and keeps the LAST call's placed batch and
    outputs (earlier outputs are donated to the next step, so only the
    last are alive after ``fit`` returns). A call that raises is counted
    in ``raised`` and re-raised. ``tracked`` is the program's handle on the
    step's compiled programs (``utils/compile.py`` ``TrackedJit``), or
    ``None``."""

    def __init__(self, model):
        self.calls = 0
        self.raised = 0
        self.batch = None
        self.outputs = None
        self.tracked = None
        build = model._build_train_step

        def spy_build(*args, **kwargs):
            run = build(*args, **kwargs)
            self.tracked = getattr(run, "_tracked", None)

            def spied(params, opt_state, aux, batch, *rest):
                self.calls += 1
                try:
                    out = run(params, opt_state, aux, batch, *rest)
                except Exception:
                    self.raised += 1
                    raise
                self.batch, self.outputs = batch, out
                return out

            spied.__dict__.update(run.__dict__)  # keeps run._tracked
            return spied

        model._build_train_step = spy_build


def placement_faults(spy, devices, platform):
    """Reasons why the last step's batch and outputs are not exactly on
    ``devices`` of ``platform``; empty when they are."""
    import jax

    faults = []
    want = set(devices)
    if any(d.platform != platform for d in devices):
        faults.append(f"cell devices are not all {platform}: {devices}")
    for what, tree in (("outputs", spy.outputs), ("batch", spy.batch)):
        leaves = [x for x in jax.tree_util.tree_leaves(tree)
                  if isinstance(x, jax.Array)]
        if not leaves:
            faults.append(f"no step {what} were seen")
        off = [x for x in leaves if set(x.devices()) != want]
        if off:
            faults.append(f"{len(off)} of {len(leaves)} step {what} leaves "
                          f"are not on {sorted(d.id for d in devices)}")
    return faults


def replica_faults(spy, devices):
    """With several chips: every parameter has a replica on every chip and
    the replicas are bitwise equal."""
    faults = []
    params = spy.outputs[0]
    for name, arr in params.items():
        shards = arr.addressable_shards
        if {s.device for s in shards} != set(devices):
            faults.append(f"{name}: no replica on every chip")
            continue
        first = np.asarray(shards[0].data).tobytes()
        if any(np.asarray(s.data).tobytes() != first for s in shards[1:]):
            faults.append(f"{name}: replicas differ")
    return faults


def train_program_text(spy):
    """``(text, why_not)``: the optimized-HLO text of the ONE train program
    the run dispatched, from the executable that is already warm (no
    lowering, no compile, no jit cache consulted), or ``None`` and the
    reason. The program's public ``TrackedJit.optimized_hlo`` wants the
    call's arguments again, which the last step donated; until it offers
    the text without them (PERF.md section 7) this reads the handle's
    table of warmed executables."""
    import jax

    if spy.tracked is None:
        return None, "the step fit built carries no _tracked handle"
    programs = list(getattr(spy.tracked, "_aot", {}).values())
    if len(programs) != 1:
        return None, (f"the handle holds {len(programs)} warmed train "
                      "programs, not one")
    try:
        text = programs[0].as_text()
    except jax.errors.JaxRuntimeError as e:
        return None, f"the runtime refused the text: {str(e).splitlines()[0]}"
    return text, None if text else "this backend gives no text"


def load_reference(config_path, config):
    """The plain reference beside the configuration's file."""
    return catalog.load_file_module(
        os.path.join(os.path.dirname(config_path), config["reference"]),
        "bench_reference_" + config["name"])


def relative_error(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def reference_error(mx, model, symbol, config, config_path, images, device,
                    compute_dtype):
    """Relative L2 error of the system's logits, computed as the cell
    computes (``compute_dtype``, the trained weights as ``fit`` wrote them
    back) on ``device``, against the configuration's plain float32
    reference of the same weights on the same rows (images, or ids)."""
    import jax

    # ``predict`` cuts every output to the batch's rows, so logits of
    # ``(rows x positions, classes)`` would come back as the first ``rows``
    # positions: both sides are compared as one row a sample, every
    # position of every sequence (for an image's logits nothing changes)
    n = len(images)
    head = mx.symbol.Reshape(data=symbol.get_internals()[config["logits"]],
                             target_shape=(n, -1))
    served = mx.FeedForward(head, ctx=mx.Context(device.platform, device.id),
                            arg_params=model.arg_params,
                            aux_params=model.aux_params,
                            compute_dtype=compute_dtype)
    got = served.predict(images, batch_size=n)
    ref = load_reference(config_path, config)
    params = {k: v.asnumpy() for k, v in model.arg_params.items()}
    aux = {k: v.asnumpy() for k, v in model.aux_params.items()}
    with jax.default_device(device), \
            jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(ref.logits)(params, aux, images))
    want = want.reshape(n, -1)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return relative_error(got, want)
