"""Per-chunk int8 quantization for streamed uploads (``enc="int8c"``).

The wire module's row-quantized ``int8`` keys its fp32 scales to the
tensor's leading axis — fine for a matrix, degenerate for the 1-D and
scalar leaves a transformer tree is full of (one scale for a whole
embedding row block, or for an entire bias vector). This codec keys the
scales to FIXED element chunks of :data:`QUANT_CHUNK_ELEMS` instead, so
every leaf — any rank, any shape — quantizes with uniform local scale
resolution and the encoded size is computable from the element count
alone (what lets a stream header plan it before any leaf is gathered).

Payload layout for a tensor of ``n`` elements::

    [ceil(n / QUANT_CHUNK_ELEMS) x fp32 scale] + [n x int8]

Each chunk's scale is ``max|chunk| / 127``; values quantize as
``clip(rint(x / scale), -127, 127)``. Overhead is one fp32 per 4096
elements (~0.1%), so the wire cost is ~4x below fp32 — the
``--wire-dtype int8`` arm tests/test_wire_efficiency.py pins.

Determinism contract (this module is in the ``fedtpu check``
determinism-pass SCOPE): both directions are pure elementwise numpy on
the input bytes — same payload in, same fp32 out, on every host and
every replay. Non-finite inputs map deterministically too: a chunk whose
max|x| is 0 or non-finite falls back to scale 1.0, NaN quantizes to 0,
±inf saturates to ±127. The server dequantizes BEFORE folding, so the
ascending-id fp32 fold order (and with it ``fleet_crc_exact`` and the
DP re-clip contract) extends to quantized rounds unchanged.
"""

from __future__ import annotations

import numpy as np

#: Elements per fp32 scale group. 4096 keeps the scale overhead at
#: ~0.1% while bounding each scale's blast radius (one outlier inflates
#: the quantization step of 4096 neighbors, not a whole tensor row).
QUANT_CHUNK_ELEMS = 4096


def int8c_nchunks(size: int) -> int:
    """Scale-group count for a tensor of ``size`` elements."""
    size = int(size)
    if size < 0:
        raise ValueError(f"negative tensor size {size}")
    return -(-size // QUANT_CHUNK_ELEMS)


def int8c_nbytes(size: int) -> int:
    """Exact encoded byte count for ``size`` elements — computable from
    shape alone, which is what makes the encoding streamable."""
    return 4 * int8c_nchunks(size) + int(size)


def quantize_int8c(arr: np.ndarray) -> bytes:
    """fp32 tensor -> ``[chunk scales fp32] + [int8 data]`` payload."""
    a = np.ascontiguousarray(arr, np.float32).reshape(-1)
    n = a.size
    if n == 0:
        return b""
    nchunks = int8c_nchunks(n)
    pad = nchunks * QUANT_CHUNK_ELEMS - n
    a2 = (np.pad(a, (0, pad)) if pad else a).reshape(
        nchunks, QUANT_CHUNK_ELEMS
    )
    with np.errstate(invalid="ignore"):
        amax = np.max(np.abs(a2), axis=1)
    scales = (amax / np.float32(127.0)).astype(np.float32)
    # A chunk of zeros/denormals (scale underflows to 0) or one holding
    # inf/NaN (scale non-finite) cannot set its own step; scale 1.0 keeps
    # both directions finite and deterministic.
    scales = np.where(
        np.isfinite(scales) & (scales > 0), scales, np.float32(1.0)
    ).astype(np.float32)
    with np.errstate(invalid="ignore", over="ignore"):
        ratio = a2 / scales[:, None]
    # NaN -> 0, +/-inf -> saturate: the deterministic non-finite mapping
    # (int8 cast of NaN is platform-defined — never let one reach it).
    ratio = np.nan_to_num(ratio, nan=0.0, posinf=127.0, neginf=-127.0)
    q = np.clip(np.rint(ratio), -127, 127).astype(np.int8)
    return scales.tobytes() + q.reshape(-1)[:n].tobytes()


def dequantize_int8c(raw, shape: tuple[int, ...]) -> np.ndarray:
    """Inverse of :func:`quantize_int8c` for a tensor of ``shape``.

    The payload is untrusted wire bytes: the length must match the shape
    exactly and every scale must be finite and positive (the encoder
    never emits anything else; a NaN scale would otherwise poison the
    round's running fold through one crafted upload)."""
    from . import wire

    size = int(np.prod(shape, dtype=np.int64)) if shape else 1
    nchunks = int8c_nchunks(size)
    want = 4 * nchunks + size
    if len(raw) != want:
        raise wire.WireError(
            f"int8c tensor payload is {len(raw)} bytes, expected {want}"
        )
    scales = np.frombuffer(raw, np.float32, count=nchunks)
    if nchunks and not bool(np.all(np.isfinite(scales) & (scales > 0))):
        raise wire.WireError(
            "int8c tensor carries a non-finite or non-positive scale"
        )
    q = np.frombuffer(raw, np.int8, count=size, offset=4 * nchunks)
    out = q.astype(np.float32) * np.repeat(scales, QUANT_CHUNK_ELEMS)[:size]
    return out.reshape(shape)
