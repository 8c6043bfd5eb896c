"""Op lowerings — importing this package registers all ops.

Capability mirror of paddle/fluid/operators/ (480 registered ops): the subset
needed by the BASELINE workload ladder plus the common API surface, each as a
JAX lowering in the registry (see core/registry.py).
"""

from . import lr_ops, math_ops, nn_ops, optimizer_ops, tensor_ops  # noqa: F401
from . import amp_ops  # noqa: F401
from . import collective_ops  # noqa: F401
from . import control_flow_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import attention_ops  # noqa: F401
from . import pipeline_ops  # noqa: F401
from . import extra_ops  # noqa: F401
from . import rnn_ops  # noqa: F401
from . import quant_ops  # noqa: F401
from . import moe_ops  # noqa: F401
from . import llm_ops  # noqa: F401
from . import ssm_ops  # noqa: F401
from . import short_conv_ops  # noqa: F401
from . import linear_attention_ops  # noqa: F401
from . import ps_ops  # noqa: F401
from . import beam_search_ops  # noqa: F401
from . import crf_ops  # noqa: F401
from . import extra_ops2  # noqa: F401
from . import extra_ops3  # noqa: F401
from . import extra_ops4  # noqa: F401
from . import io_ops  # noqa: F401
from . import fused_ops  # noqa: F401
from . import fused_rnn_ops  # noqa: F401
from . import contrib_ops  # noqa: F401
from . import interp_ops  # noqa: F401
from . import linalg_ops  # noqa: F401
from . import metrics_ops  # noqa: F401
from . import vision_ops  # noqa: F401
