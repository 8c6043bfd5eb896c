"""paddle_tpu — a TPU-native deep-learning framework with the capability
surface of PaddlePaddle Fluid (reference at /root/reference), built on
JAX/XLA/Pallas/pjit.

Architecture (see SURVEY.md for the full blueprint):
  * Python builds a Program (Block ⊃ OpDescs) — reference framework.proto IR.
  * Ops are JAX lowerings in a registry; autodiff appends grad ops
    (program-level, like backward.py) with a generic jax.vjp grad op.
  * The compiling Executor lowers a whole block to ONE jitted XLA
    computation (the ParallelExecutor/BuildStrategy role); an interpreting
    executor is the correctness oracle.
  * Parallelism = jax.sharding over a Mesh (DP/TP/PP/SP), not per-device
    graph replication; collective ops lower to psum/all_gather/ppermute.
"""

from .core.compile_cache import enable_compile_cache as _enable_cache

_enable_cache()     # persistent XLA compile cache, one fixed directory

from . import initializer, layers, optimizer, regularizer  # noqa: F401,E402
from . import clip  # noqa: F401
from . import io  # noqa: F401
from . import amp  # noqa: F401
from . import contrib  # noqa: F401
from . import metric  # noqa: F401
from . import reader  # noqa: F401
from .reader import DataLoader  # noqa: F401

io.DataLoader = DataLoader  # fluid.io.DataLoader compat
from . import ops as _ops  # registers all op lowerings  # noqa: F401
from .core import (CPUPlace, CUDAPlace, Executor, Parameter, Program,  # noqa: F401
                   Scope, TPUPlace, Variable, XLAPlace, append_backward,
                   default_main_program, default_startup_program, device_guard,
                   global_scope, gradients, in_dygraph_mode, program_guard)
from .core.compiler import BuildStrategy, CompiledProgram, ExecutionStrategy  # noqa: F401
from .core.executor import run_startup  # noqa: F401
from .core.verify import ProgramVerifyError, verify_program  # noqa: F401
from .core.analysis import LockOrderError, install_thread_excepthook  # noqa: F401

# worker threads must never die silently: every uncaught exception in a
# thread books threads.uncaught_exceptions + a thread_error run-log
# record before the default stderr print (core/analysis/lockdep.py)
install_thread_excepthook()
# flight recorder (core/incidents.py): importing it installs the
# always-on black-box tap on telemetry.emit, so every process keeps the
# last FLAGS_blackbox_seconds of telemetry/span history in memory for
# anomaly-triggered incident dumps
from .core import incidents as _incidents  # noqa: F401,E402
# the planes built on the hot paths' two telemetry hooks attach themselves
# as they are imported: core/trace.py to timer(span=), core/goodput.py and
# core/incidents.py to tick(). The executor and the decode engine import
# none of them.
from .core import goodput as _goodput, trace as _trace  # noqa: F401,E402
from .param_attr import ParamAttr  # noqa: F401
from . import dataset  # noqa: F401  (native-backed Dataset API)
from .dataset import DatasetFactory, InMemoryDataset, QueueDataset  # noqa: F401
from . import profiler  # noqa: F401
from .core.flags import get_flags, set_flags  # noqa: F401
from .core import monitor  # noqa: F401
from . import utils  # noqa: F401
from . import generator  # noqa: F401
from .generator import seed  # noqa: F401
from . import checkpoint  # noqa: F401
from . import vision  # noqa: F401
from . import text  # noqa: F401
from . import tensor  # noqa: F401
from . import static  # noqa: F401
from .static import disable_static, enable_static  # noqa: F401
from . import dygraph  # noqa: F401
from .dygraph import jit  # noqa: F401
from .tensor import to_tensor  # noqa: F401


def summary(net, input_size, dtypes=None):
    """paddle.summary — per-layer table for a dygraph Layer
    (reference: hapi/model_summary.py)."""
    from .hapi import summary as _summary

    return _summary(net, input_size, dtypes=dtypes)


__version__ = "0.1.0"

# fluid-compat namespace: `import paddle_tpu.fluid as fluid` style usage is
# served by this module itself (fluid == paddle_tpu).
fluid = __import__(__name__)


def data(name, shape, dtype="float32", lod_level=0):
    """paddle.static.data — full shape, no implicit batch dim."""
    return layers.static_data(name, shape, dtype, lod_level)


def set_global_seed(seed: int):
    default_main_program().random_seed = seed
    default_startup_program().random_seed = seed
