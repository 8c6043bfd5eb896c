"""Core type system for the TPU-native framework.

Mirrors the capability of the reference's VarType proto
(paddle/fluid/framework/framework.proto:104 — 21 var kinds) and the Place
hierarchy (paddle/fluid/platform/place.h:26-125), re-designed for JAX/XLA:
a Place wraps a `jax.Device` set, and dtypes are numpy/jax dtypes rather
than a proto enum.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass
from typing import Any, Optional

import numpy as np


class VarType(enum.Enum):
    """Variable kinds (reference: framework.proto VarType::Type)."""

    DENSE_TENSOR = "dense_tensor"        # reference LOD_TENSOR (lod_level==0 common case)
    SELECTED_ROWS = "selected_rows"      # sparse (ids, values) pair
    TENSOR_ARRAY = "tensor_array"        # reference LOD_TENSOR_ARRAY
    STEP_SCOPES = "step_scopes"          # control-flow sub-scope holder
    READER = "reader"                    # data pipeline endpoint
    RAW = "raw"                          # opaque (generator state, comm handles)

    # Back-compat alias used throughout fluid
    LOD_TENSOR = "dense_tensor"


# dtype canonicalisation -----------------------------------------------------

_DTYPE_ALIASES = {
    "float32": np.dtype("float32"),
    "fp32": np.dtype("float32"),
    "float64": np.dtype("float64"),
    "fp64": np.dtype("float64"),
    "float16": np.dtype("float16"),
    "fp16": np.dtype("float16"),
    "bfloat16": "bfloat16",  # resolved lazily via ml_dtypes/jax
    "bf16": "bfloat16",
    "int8": np.dtype("int8"),
    "uint8": np.dtype("uint8"),
    "int16": np.dtype("int16"),
    "int32": np.dtype("int32"),
    "int64": np.dtype("int64"),
    "bool": np.dtype("bool"),
}


def convert_dtype(dtype: Any) -> np.dtype:
    """Canonicalise any dtype spec (string alias, np/jnp dtype) to np.dtype."""
    if dtype is None:
        return np.dtype("float32")
    if isinstance(dtype, str):
        resolved = _DTYPE_ALIASES.get(dtype, dtype)
        if resolved == "bfloat16":
            import ml_dtypes

            return np.dtype(ml_dtypes.bfloat16)
        return np.dtype(resolved)
    # jnp.bfloat16 etc. pass through np.dtype fine
    return np.dtype(dtype)


def is_floating(dtype: Any) -> bool:
    d = convert_dtype(dtype)
    if d.kind == "f":
        return True
    # bfloat16 has kind 'V' in some numpy versions
    return "bfloat16" in str(d)


def bf16() -> np.dtype:
    return convert_dtype("bfloat16")


# Place kinds ----------------------------------------------------------------


@dataclass(frozen=True)
class Place:
    """Device identity (reference: platform/place.h Place boost::variant).

    On TPU builds the interesting axis is cpu-vs-tpu; device_id selects a
    chip within the local process.
    """

    device_type: str = "cpu"  # "cpu" | "tpu" | "gpu" (alias of accelerator)
    device_id: int = 0

    def is_cpu_place(self) -> bool:
        return self.device_type == "cpu"

    def is_tpu_place(self) -> bool:
        return self.device_type == "tpu"

    def jax_device(self):
        import jax

        if self.device_type == "cpu":
            return jax.devices("cpu")[0]
        devs = jax.devices()
        return devs[self.device_id % len(devs)]

    def __repr__(self) -> str:  # paddle-style repr
        if self.device_type == "cpu":
            return "CPUPlace"
        return f"{self.device_type.upper()}Place({self.device_id})"


def CPUPlace() -> Place:
    return Place("cpu", 0)


def TPUPlace(device_id: int = 0) -> Place:
    return Place("tpu", device_id)


# CUDAPlace alias keeps fluid-era user code importable; it maps to the
# process's accelerator (TPU) — there is no CUDA in this framework.
def CUDAPlace(device_id: int = 0) -> Place:
    return Place("tpu", device_id)


def XLAPlace(device_id: int = 0) -> Place:
    return Place("tpu", device_id)


def default_place() -> Place:
    import jax

    # a backend that fails to initialise is an error, not a CPUPlace
    if jax.default_backend() == "cpu":
        return CPUPlace()
    return TPUPlace(0)


# Core data-holder names used in scopes --------------------------------------

STEP_COUNTER_VAR = "@STEP_COUNTER@"  # implicit per-run step for RNG folding
LOSS_SCALING_VAR = "@LOSS_SCALING@"


class DataLayout(enum.Enum):
    NCHW = "NCHW"
    NHWC = "NHWC"
    ANY = "ANY"
