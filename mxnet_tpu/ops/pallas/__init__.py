"""Pallas TPU kernels for the hot ops — the hand-written kernel layer.

The reference's answer to "the op is the bottleneck" is a hand-written
CUDA kernel behind mshadow (SURVEY.md §2.7); ours is a Pallas kernel that
tiles onto the MXU/VPU with VMEM-resident blocks. Only ops where XLA
fusion is insufficient get a kernel (pallas_guide.md playbook);
everything else stays jax.numpy.

Kernels (catalog: doc/developer-guide/kernels.md):

  flash_attention     blocked online-softmax attention, O(seq) memory,
                      custom VJP with Pallas forward/backward kernels.
  comm_kernels        fused gradient quantize/dequantize for the
                      compressed allreduce: payload + per-chunk scales
                      (+ error-feedback round-trip) in one VMEM pass,
                      and the inverse dequant + f32-accumulate.
  adam                the whole Adam/AdamW update as one blocked pass
                      over the flattened (param, grad, m, v) slab —
                      bitwise parity with the per-leaf optimizer.
  matmul              int8 matmul (per-channel scales, int32 accumulate)
                      for the serving/predict path.
  moe                 an expert layer's rows to its expert-sorted space and
                      back, the work following the count of picks held
                      in the step (a prefetched scalar), not the space.

Infrastructure:

  registry            every kernel registers its FLOP/byte model, keyed
                      by its pallas_call ``name=``; the jaxpr auditor
                      attributes kernel regions through it so MFU and
                      the jaxpr cost table stop under-counting custom
                      kernels (mxlint MX312 keeps the discipline).
  _common             the ONE interpret-mode gate: off-TPU backends run
                      every kernel through the Pallas interpreter, so
                      unit tests exercise the real kernel code paths on
                      the 8-device CPU mesh (``MXNET_TPU_PALLAS_INTERPRET``
                      overrides there); a TPU process always compiles.
"""

from ._common import resolve_interpret, use_interpret  # noqa: F401
from .adam import fused_adam_apply, fused_resolve  # noqa: F401
from .comm_kernels import (  # noqa: F401
    fused_dequant,
    fused_dequant_sum,
    fused_quantize,
)
from .flash_attention import (  # noqa: F401
    flash_attention,
    flash_attention_with_lse,
    flash_block_grads,
)
from .matmul import (  # noqa: F401
    int8_matmul,
    int8_predict_active,
    int8_predict_scope,
    quantize_channels,
)
from .moe import moe_combine, moe_dispatch  # noqa: F401
from .registry import (  # noqa: F401
    KernelCost,
    attribute_eqn,
    catalog,
    kernel_cost,
    kernel_names,
    kernels,
    register_kernel,
)

__all__ = [
    "flash_attention", "flash_attention_with_lse", "flash_block_grads",
    "fused_quantize", "fused_dequant_sum", "fused_dequant",
    "fused_adam_apply", "fused_resolve",
    "int8_matmul", "quantize_channels", "int8_predict_scope",
    "int8_predict_active",
    "moe_dispatch", "moe_combine",
    "KernelCost", "register_kernel", "kernel_cost", "kernel_names",
    "kernels", "attribute_eqn", "catalog",
    "use_interpret", "resolve_interpret",
]
