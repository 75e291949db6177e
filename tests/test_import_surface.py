"""Import-surface guard (ISSUE 1 satellite).

The seed's single unchecked API drift (``from jax import shard_map``)
surfaced as 75 opaque pytest collection errors. This test imports every
``mxnet_tpu.*`` submodule under the CPU platform, so any future drift —
a moved JAX symbol, a typo'd import, a missing optional dep leaking into a
module scope — fails exactly ONE obvious test naming the broken module.
"""

import importlib
import pkgutil

import pytest

import mxnet_tpu

# modules whose import has side effects that need env not present in unit
# tests (none today; keep the hook so future additions are explicit)
_SKIP: set[str] = set()


def _all_submodules():
    mods = ["mxnet_tpu"]
    for info in pkgutil.walk_packages(mxnet_tpu.__path__,
                                      prefix="mxnet_tpu."):
        # native/libmxtpu_*.so are ctypes payloads (loaded via CDLL), not
        # Python extension modules — pkgutil lists them anyway
        if info.name.rsplit(".", 1)[-1].startswith("lib"):
            continue
        mods.append(info.name)
    return sorted(mods)


@pytest.mark.parametrize("module_name", _all_submodules())
def test_submodule_imports(module_name):
    if module_name in _SKIP:
        pytest.skip(f"{module_name}: explicit skip")
    importlib.import_module(module_name)


def test_walk_found_the_tree():
    """The walk itself must see the package layout (a packaging regression
    that hides submodules would otherwise pass vacuously)."""
    mods = _all_submodules()
    for expected in ("mxnet_tpu.symbol", "mxnet_tpu.executor",
                     "mxnet_tpu.compat", "mxnet_tpu.analysis",
                     "mxnet_tpu.analysis.source_lint",
                     "mxnet_tpu.models.transformer",
                     "mxnet_tpu.parallel.sequence"):
        assert expected in mods, f"{expected} missing from package walk"
    assert len(mods) > 40


def test_shard_map_compat_shim():
    """compat.shard_map is the installed JAX's own shard_map (one pinned
    version, no translation layer)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.compat import shard_map
    from mxnet_tpu.parallel import make_mesh

    assert shard_map is jax.shard_map
    mesh = make_mesh(dp=8)
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    for flag in ({"check_vma": False}, {}):
        out = shard_map(lambda v: v * 2, mesh=mesh, in_specs=P("dp"),
                        out_specs=P("dp"), **flag)(x)
        np.testing.assert_allclose(np.asarray(out), x * 2)
