"""Operator library. Importing this package registers all ops.

≙ reference paddle/fluid/operators/ (~264 registered op types; static
registration via REGISTER_OPERATOR, op_registry.h:136). Here registration is
import-time Python decoration — same effect, no static-initializer dance.
"""

from . import math_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import control_ops  # noqa: F401
from . import flow_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import rnn_ops  # noqa: F401
from . import beam_ops  # noqa: F401
from . import crf_ops  # noqa: F401
from . import attention_ops  # noqa: F401
from . import block_sparse_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import misc_ops  # noqa: F401
from . import pipeline_ops  # noqa: F401
from . import moe_ops  # noqa: F401
from . import volumetric_ops  # noqa: F401
from . import fused_ops  # noqa: F401

from ..core.registry import registered_ops  # noqa: F401
