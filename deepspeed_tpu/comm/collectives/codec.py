"""Wire codec: blockwise-scale quantization for compressed collectives.

One codec, every caller: ZeRO++ qwZ/qgZ (``runtime/zero/zeropp.py``),
the 1-bit-family error-feedback all-reduce (``runtime/comm/compressed.py``),
MoE expert dispatch (``moe/ep_dispatch.py``), and ring attention
(``sequence/ring_attention.py``) all compress through these two functions,
so the wire format is defined exactly once.

Formats (``CompressionSpec.format``):
  ``int8`` — symmetric per-block int8 codes + one fp32 scale per block
    (scale = max|block| / 127).  ~3.9x fewer wire bytes than fp32 at
    128-block granularity; the ZeRO++ / EQuARX workhorse.
  ``fp8``  — float8_e4m3fn codes + one fp32 scale per block
    (scale = max|block| / 448, the e4m3 max-finite).  Same wire volume as
    int8 with a wider dynamic range within the block; gated on the jax
    build exposing ``jnp.float8_e4m3fn``.

Quantization runs along the LAST dim, padded up to a whole number of
blocks; callers with small trailing dims (attention heads) reshape to a
fused last dim first.  Error-feedback residuals are *caller-owned state*:
the codec exposes the compensate/residual arithmetic, the caller carries
the buffer (optimizer state, train-state leaf, closure carry) — nothing
here is stateful, everything traces into the program.

The int8 math is bit-identical to the original
``runtime/zero/zeropp.quantize_lastdim`` (which now delegates here), so
the checked-in HLO cost contracts for the qgZ programs hold unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp

#: default quantization block (reference csrc/quantization group size)
DEFAULT_BLOCK = 128

#: fp8 code dtype
FP8_DTYPE = jnp.float8_e4m3fn
_FP8_MAX = 448.0  # e4m3fn largest finite

_FORMATS = ("int8", "fp8")


@dataclasses.dataclass(frozen=True)
class CompressionSpec:
    """How a collective's payload rides the wire.

    Frozen (hashable) so it can be a ``custom_vjp`` nondiff argument and
    a jit-static closure value.
    """

    format: str = "int8"  # int8 | fp8
    block: int = DEFAULT_BLOCK
    #: carry a caller-owned residual: the compressed verbs then take and
    #: return an ``error`` buffer alongside the result
    error_feedback: bool = False
    #: differentiated verbs (``all_to_all``, ``ppermute``): also quantize
    #: the BACKWARD exchange — the custom_vjp applies the codec to the
    #: transposed permute/a2a instead of moving the exact cotangent.
    #: Off by default (the PR-11 straight-through contract); callers that
    #: turn it on can carry a residual slot via the ``error=`` variants.
    compress_backward: bool = False

    def __post_init__(self):
        if self.format not in _FORMATS:
            raise ValueError(
                f"CompressionSpec.format must be one of {_FORMATS}, "
                f"got {self.format!r}")
        if self.block <= 0:
            raise ValueError(f"CompressionSpec.block must be > 0, "
                             f"got {self.block}")

    @classmethod
    def parse(cls, value: Union[None, str, dict, "CompressionSpec"]
              ) -> Optional["CompressionSpec"]:
        """Coerce config-surface values: None | "int8"/"fp8" | kwargs dict
        | an already-built spec."""
        if value is None:
            return None
        if isinstance(value, cls):
            return value
        if isinstance(value, str):
            return cls(format=value)
        if isinstance(value, dict):
            return cls(**value)
        raise TypeError(f"cannot parse a CompressionSpec from "
                        f"{type(value).__name__}: {value!r}")


def _code_dtype(spec: CompressionSpec):
    return jnp.int8 if spec.format == "int8" else FP8_DTYPE


def quantize_blockwise(x: jnp.ndarray, spec: CompressionSpec
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Blockwise quantize along the last dim, keeping array rank.

    Returns ``(codes [..., Dpad], scales fp32 [..., Dpad/block], D)``
    where ``D`` is the original last-dim size (dequantize slices the pad
    back off).
    """
    b = spec.block
    d = x.shape[-1]
    pad = (-d) % b
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    blocks = x.reshape(*x.shape[:-1], x.shape[-1] // b, b)
    blocks = blocks.astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(blocks), -1), 1e-12)
    if spec.format == "int8":
        scale = absmax / 127.0
        q = jnp.clip(jnp.round(blocks / scale[..., None]), -127, 127)
        codes = q.reshape(*x.shape).astype(jnp.int8)
    else:
        scale = absmax / _FP8_MAX
        codes = (blocks / scale[..., None]).reshape(*x.shape).astype(FP8_DTYPE)
    return codes, scale, d


def dequantize_blockwise(codes: jnp.ndarray, scales: jnp.ndarray, d: int,
                         dtype: Any = jnp.bfloat16) -> jnp.ndarray:
    """Inverse of :func:`quantize_blockwise` (block size is implied by the
    codes/scales shapes, so one dequantizer serves every format)."""
    b = codes.shape[-1] // scales.shape[-1]
    blocks = codes.reshape(*codes.shape[:-1], codes.shape[-1] // b, b)
    x = blocks.astype(jnp.float32) * scales[..., None]
    x = x.reshape(*codes.shape)
    if d != codes.shape[-1]:
        x = x[..., :d]
    return x.astype(dtype)


def qdq(x: jnp.ndarray, spec: CompressionSpec) -> jnp.ndarray:
    """Quantize-dequantize round trip in the caller's dtype — the value a
    peer reconstructs from this rank's wire payload.  Error feedback keeps
    ``compensated - qdq(compensated)`` as the next step's residual."""
    codes, scales, d = quantize_blockwise(x, spec)
    return dequantize_blockwise(codes, scales, d, x.dtype)


def compensate(x: jnp.ndarray, error: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Fold the carried residual into this round's payload."""
    return x if error is None else x + error.astype(x.dtype)


def wire_bytes(codes: jnp.ndarray, scales: jnp.ndarray) -> int:
    """Bytes this payload puts on the wire (codes + block scales)."""
    return (codes.size * jnp.dtype(codes.dtype).itemsize
            + scales.size * jnp.dtype(scales.dtype).itemsize)


def logical_bytes(x: jnp.ndarray) -> int:
    """Bytes the uncompressed payload would have moved."""
    return x.size * jnp.dtype(getattr(x, "dtype", jnp.float32)).itemsize


def init_error(x: jnp.ndarray) -> jnp.ndarray:
    """A fresh error-feedback buffer for payload ``x`` (caller-owned;
    thread it through optimizer/train state)."""
    return jnp.zeros_like(x)
