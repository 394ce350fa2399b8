"""Core scalar/variable type definitions.

TPU-native analogue of the reference's dtype/VarType enums
(reference: paddle/fluid/framework/framework.proto:91-117 `VarType`,
paddle/fluid/framework/data_type.h). We keep the same *capability surface*
(a serializable dtype tag per variable) but represent dtypes directly as
numpy/jax dtype strings — there is no proto layer because the IR serializes
to JSON (see core/program.py).
"""

from __future__ import annotations

import numpy as np

# Canonical dtype strings. bfloat16 replaces the reference's float16 focus
# (platform/float16.h) because bf16 is the TPU-native half type (MXU input).
DTYPES = (
    "float32",
    "float64",
    "bfloat16",
    "float16",
    "int8",
    "int32",
    "int64",
    "uint8",
    "bool",
)


def normalize_dtype(dtype) -> str:
    """Map a numpy/jax/python dtype-like to a canonical dtype string."""
    if isinstance(dtype, str):
        name = dtype
    else:
        try:
            name = np.dtype(dtype).name
        except TypeError:
            name = getattr(dtype, "name", None) or str(dtype)
    if name == "bfloat16" or "bfloat16" in name:
        name = "bfloat16"
    aliases = {"float": "float32", "double": "float64", "int": "int32", "long": "int64"}
    name = aliases.get(name, name)
    if name not in DTYPES:
        raise ValueError(f"unsupported dtype {dtype!r} (normalized {name!r})")
    return name


def device_dtype(dtype: str) -> str:
    """64-bit host dtypes narrow to 32-bit on device (TPU-native widths).
    The single owner of the narrowing policy — executor feeds, op kernels,
    and memory init all route through here."""
    return {"int64": "int32", "float64": "float32"}.get(dtype, dtype)


def np_dtype(dtype: str):
    """Canonical dtype string -> numpy dtype (bfloat16 via ml_dtypes)."""
    if dtype == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(dtype)


def is_floating(dtype: str) -> bool:
    return dtype in ("float32", "float64", "bfloat16", "float16")


# --- on-wire feed codec (data/codec.py) ------------------------------------
# The host->device feed pipe is the bottleneck on thin-pipe hosts (an
# earlier remote set-up: ~15 MB/s capped real-data training at 245 img/s), so
# batches may cross the wire ENCODED and dequantize on device. These two
# facts live here — not in data/codec.py — because the core layers
# (executor feed prep, lowering's AMP entry cast, the feed_dequant op)
# must know them without importing the data package.

#: codec policy -> the dtype that actually crosses the host->device wire.
#: "none" = raw passthrough; "bf16" = truncate f32 to bfloat16 (2x fewer
#: bytes); "int8" = per-channel symmetric int8 (4x, plus a tiny f32 scale
#: companion per channel).
WIRE_DTYPES = {"none": None, "bf16": "bfloat16", "int8": "int8"}

#: name suffix of the per-channel scale companion feed that rides beside
#: an int8-encoded feed. The lowering exempts these from the AMP entry
#: cast (dequant scales must stay f32) and the executor materializes them
#: when it host-encodes a raw feed.
CODEC_SCALE_SUFFIX = "__codec_scale"


def wire_dtype_of(policy: str) -> str:
    """Wire dtype for a codec policy; raises on unknown policies so a
    typo'd PT_FEED_CODEC fails loudly instead of silently passing raw."""
    try:
        return WIRE_DTYPES[policy]
    except KeyError:
        raise ValueError(
            f"unknown feed-codec policy {policy!r} "
            f"(know {sorted(WIRE_DTYPES)})") from None


# Variable kinds — the subset of the reference's VarType::Type that survives
# the move to a functional runtime. LOD_TENSOR/SELECTED_ROWS collapse into
# DENSE (ragged sequences are dense values + explicit length/offset vars,
# SURVEY.md §5 "long context"); READER/CHANNEL machinery is host-side Python.
class VarKind:
    DENSE = "dense"          # jax array in the scope
    STEP_SCOPES = "steps"    # control-flow internal
    READER = "reader"        # host-side data pipeline handle
    RAW = "raw"              # opaque host object
