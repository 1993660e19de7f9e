"""Operator package: importing this module registers all operators."""
from .registry import (OperatorProperty, register_op, create_operator,
                       OP_REGISTRY, IncompleteShape)
from . import tensor  # noqa: F401
from . import nn      # noqa: F401
from . import loss    # noqa: F401
from . import sequence  # noqa: F401
from . import rnn     # noqa: F401
from . import vision  # noqa: F401
from . import attention  # noqa: F401
from . import moe     # noqa: F401
from . import linear_attention  # noqa: F401
