"""Standalone inference predictor.

Reference counterpart: include/mxnet/c_predict_api.h + src/c_api/
c_predict_api.cc — the dependency-free deployment surface (load symbol JSON +
param blob, bind forward-only, set_input/forward/get_output) that the
amalgamation build ships. Here the deployment artifact is the same pair of
files the trainer checkpoints (`prefix-symbol.json` + `prefix-%04d.params`);
the "minimal runtime" is jax's compiled executable, and `export`/`load`
produce a single-file bundle (the amalgamation-equivalent, one .npz holding
graph + params).
"""

from __future__ import annotations

import json
import zipfile

import jax
import jax.numpy as jnp
import numpy as np

from . import ndarray as nd
from . import symbol as sym_mod
from .base import MXNetError
from .context import Context, cpu
from .executor import _build_graph_fn

__all__ = ["Predictor"]


class Predictor:
    """Forward-only bound model (reference: MXPredCreate/Forward/GetOutput)."""

    def __init__(self, symbol, arg_params, aux_params=None, ctx=None,
                 input_names=("data",), compute_dtype=None, quantize=None):
        if isinstance(symbol, str):
            symbol = sym_mod.load_json(symbol) if symbol.lstrip().startswith("{") \
                else sym_mod.load(symbol)
        self.symbol = symbol
        self.ctx = ctx or cpu()
        self.input_names = list(input_names)
        self.compute_dtype = compute_dtype
        # quantize="int8": serve FullyConnected matmuls through the int8
        # Pallas kernel (per-channel weight scales, int32 accumulate; see
        # ops/pallas/matmul.py). The gate is trace-time, so forward()
        # wraps the jit dispatch in the scope — the first call traces the
        # quantized program, later calls reuse it.
        if quantize not in (None, False, "int8"):
            raise MXNetError(f"Predictor quantize= must be None or 'int8', "
                             f"got {quantize!r}")
        self.quantize = quantize or None
        dev = self.ctx.jax_device
        self._params = {k: jax.device_put(np.asarray(v.asnumpy() if hasattr(v, "asnumpy") else v), dev)
                        for k, v in arg_params.items()}
        self._aux = {k: jax.device_put(np.asarray(v.asnumpy() if hasattr(v, "asnumpy") else v), dev)
                     for k, v in (aux_params or {}).items()}
        self._inputs = {}
        self._outputs = None
        self._label_cache = {}
        graph_fn = _build_graph_fn(symbol, is_train=False)
        zero_key = jnp.zeros((2,), jnp.uint32)
        cdt = compute_dtype

        def fwd(params, aux, inputs):
            if cdt is not None:
                params = {k: (v.astype(cdt) if jnp.issubdtype(v.dtype, jnp.floating) else v)
                          for k, v in params.items()}
                inputs = {k: (v.astype(cdt) if jnp.issubdtype(v.dtype, jnp.floating) else v)
                          for k, v in inputs.items()}
            outs, _ = graph_fn({**params, **inputs}, aux, zero_key)
            return tuple(o.astype(jnp.float32) for o in outs)

        self._fwd = jax.jit(fwd)

    # -- reference-API surface ------------------------------------------------
    @staticmethod
    def create(prefix: str, epoch: int, ctx=None, **kwargs) -> "Predictor":
        """From a training checkpoint pair (reference: MXPredCreate)."""
        from .model import load_checkpoint

        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return Predictor(symbol, arg_params, aux_params, ctx=ctx, **kwargs)

    def set_input(self, name, value):
        if hasattr(value, "asnumpy"):
            value = value.asnumpy()
        self._inputs[name] = jax.device_put(
            np.asarray(value, np.float32), self.ctx.jax_device)

    def forward(self, **inputs):
        for k, v in inputs.items():
            self.set_input(k, v)
        missing = self._fill_labels()
        if self.quantize == "int8":
            from .ops.pallas.matmul import int8_predict_scope

            with int8_predict_scope():
                self._outputs = self._fwd(self._params, self._aux,
                                          {**self._inputs, **missing})
        else:
            self._outputs = self._fwd(self._params, self._aux,
                                      {**self._inputs, **missing})
        return self

    def _fill_labels(self):
        # cached per input-shape signature: shape inference walks the whole
        # graph, far too heavy for a per-request serving loop
        sig = tuple(sorted((k, tuple(v.shape)) for k, v in self._inputs.items()))
        if sig in self._label_cache:
            return self._label_cache[sig]
        arg_names = self.symbol.list_arguments()
        provided = set(self._params) | set(self._inputs)
        missing = [n for n in arg_names if n not in provided]
        if not missing:
            self._label_cache[sig] = {}
            return {}
        known = {k: tuple(v.shape) for k, v in self._inputs.items()}
        known.update({k: tuple(v.shape) for k, v in self._params.items()
                      if k in arg_names})
        arg_shapes, _, _ = self.symbol.infer_shape(**known)
        shape_of = dict(zip(arg_names, arg_shapes))
        result = {n: jnp.zeros(shape_of[n], jnp.float32) for n in missing}
        self._label_cache[sig] = result
        return result

    def get_output(self, index=0) -> np.ndarray:
        if self._outputs is None:
            raise MXNetError("call forward() first")
        return np.asarray(self._outputs[index])

    # -- single-file bundle (≙ amalgamation deployment artifact) --------------
    def export(self, path: str):
        """Write one self-contained .mxtpu file: symbol JSON + all params."""
        with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
            z.writestr("symbol.json", self.symbol.tojson())
            manifest = {"inputs": self.input_names,
                        "params": sorted(self._params),
                        "aux": sorted(self._aux)}
            z.writestr("manifest.json", json.dumps(manifest))
            for k, v in self._params.items():
                z.writestr(f"params/{k}.npy", _npy_bytes(np.asarray(v)))
            for k, v in self._aux.items():
                z.writestr(f"aux/{k}.npy", _npy_bytes(np.asarray(v)))

    @staticmethod
    def load(path: str, ctx=None, **kwargs) -> "Predictor":
        import io as pyio

        with zipfile.ZipFile(path) as z:
            symbol = sym_mod.load_json(z.read("symbol.json").decode())
            manifest = json.loads(z.read("manifest.json"))
            params = {k: nd.array(np.load(pyio.BytesIO(z.read(f"params/{k}.npy"))))
                      for k in manifest["params"]}
            aux = {k: nd.array(np.load(pyio.BytesIO(z.read(f"aux/{k}.npy"))))
                   for k in manifest["aux"]}
        return Predictor(symbol, params, aux, ctx=ctx,
                         input_names=manifest["inputs"], **kwargs)


def _npy_bytes(arr: np.ndarray) -> bytes:
    import io as pyio

    buf = pyio.BytesIO()
    np.save(buf, arr)
    return buf.getvalue()
