"""What the chunked scan of a Mamba-2 layer must do, from its shapes, by
the rules of ``kernel_costs.py``: operations by ``flops.py``'s counts (a
causal product within a chunk is credited at the ``(chunk + 1) / 2``
positions a position reads in the mean; backward = 2 x forward; nothing
recomputed counts) and bytes as every tensor the scan must read and write,
once. A copy of what ``mxnet_tpu/ops/ssm.py`` computes under the scope
``ssd`` of ``Mamba2Mixer``, kept with the benchmark so that a PR that
changes the scan cannot change its yardstick.

``SCOPE`` is everything a Mamba-2 layer's nodes emit in the train
program's HLO (``scopes.py``): its input projection and the split of its
columns, the mixer, its output projection. ``SCAN`` is the scan's part of
the mixer: forward ``jvp(<node>/Mamba2Mixer)/ssd/...``, backward and the
recomputed forward ``transpose(jvp(...))/checkpoint/[rematted_computation
/]<node>/Mamba2Mixer/ssd/...``.
"""

SCOPE = r"layer\d+_mamba"
SCAN = r"layer\d+_mamba/Mamba2Mixer\)?/ssd/"


def products(positions, heads, head_dim, groups, state, chunk):
    """The scan's four products over ``positions`` (one sequence or
    several, whole chunks), each ``(name, cin, cout, rows)`` as a
    ``flops.py`` ``matmul`` entry: the scores ``C B^T`` of a position
    against the earlier ones of its chunk, a group; the weighted sum
    ``(L o C B^T) X`` of them, a head; each chunk's own state ``B^T X``
    (a position's ``head_dim x state`` outer product, a head); the
    carried state's part ``C S``, a head."""
    reads = (chunk + 1) / 2
    return [("ssd_scores", state, reads, positions * groups),
            ("ssd_diagonal", reads, head_dim, positions * heads),
            ("ssd_states", head_dim, state, positions * heads),
            ("ssd_carried", state, head_dim, positions * heads)]


def ssd(positions, heads, head_dim, groups, state, chunk, itemsize=2):
    """``(forward, backward)``, each ``{"flops", "bytes"}``, of the scan of
    one Mamba-2 layer over ``positions``. Forward reads ``x`` (heads x
    head_dim a position), ``B`` and ``C`` (groups x state each) in the
    compute type and the step sizes (heads, float32) and writes ``y``;
    backward reads them and ``dy`` and writes ``dx``, ``dB``, ``dC`` and
    the step sizes' gradient. The states between chunks are the scan's
    own and cost no bytes at the least."""
    macs = sum(cin * cout * rows for _, cin, cout, rows in products(
        positions, heads, head_dim, groups, state, chunk))
    x = y = positions * heads * head_dim * itemsize
    bc = 2 * positions * groups * state * itemsize
    dt = positions * heads * 4
    return ({"flops": 2 * macs, "bytes": x + bc + dt + y},
            {"flops": 4 * macs, "bytes": 2 * (x + bc + dt) + y})
