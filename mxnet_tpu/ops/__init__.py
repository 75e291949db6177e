"""Operator library: importing this package registers every operator.

Reference counterpart: src/operator/ (23 MXNET_REGISTER_OP_PROPERTY ops) plus
the TBlob-registry unary ops (src/ndarray/unary_function-inl.h). See
registry.py for the OpProp contract.
"""

from .registry import OPS, OpProp, REQUIRED, TupleParam, register_op
from . import tensor  # noqa: F401  (registration side effects)
from . import nn  # noqa: F401
from . import loss  # noqa: F401
from . import decoder  # noqa: F401
from . import native  # noqa: F401

__all__ = ["OPS", "OpProp", "REQUIRED", "TupleParam", "register_op"]
