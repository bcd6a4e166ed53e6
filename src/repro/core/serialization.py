"""Fast serialization, adapted from wire formats to TPU collectives.

The paper's fast serialization strips Protobuf's per-field tags and wire types
(fields are always serialized in a fixed order), halving small-message sizes —
for an (int, int) key/value pair: 2 bytes instead of Protobuf's 4.

Under XLA there is no user-visible byte stream: the controllable quantities are
the *element type* and *element count* that collectives move over ICI/DCN.
This module is therefore two things:

1. **The TPU analogue** — dtype narrowing and quantization used by
   ``distributed.collectives.compressed_psum`` and by the MapReduce shuffle:
   * positional (dense) keys: key bytes on the wire are ZERO — the accumulator
     index *is* the key, the logical endpoint of "no tags, fixed field order";
   * narrow explicit keys: int64 → smallest int dtype covering the key range;
   * value narrowing: f32 → bf16, or int8 + per-block scale, with
     error-feedback residuals so iterative algorithms stay unbiased.

2. **A faithful host-side reference** of the paper's byte-level format
   (varint, tag-free, fixed field order) next to a Protobuf-style tagged
   encoding, with which ``tests/test_serialization.py`` checks the paper's
   message-size claims analytically.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# ---------------------------------------------------------------------------
# 1) TPU-side narrowing / quantization (used on the collective path)
# ---------------------------------------------------------------------------


def narrowest_int_dtype(key_range: int) -> jnp.dtype:
    """Smallest integer dtype that can index ``key_range`` dense keys."""
    if key_range <= (1 << 7):
        return jnp.dtype(jnp.int8)
    if key_range <= (1 << 15):
        return jnp.dtype(jnp.int16)
    if key_range <= (1 << 31):
        return jnp.dtype(jnp.int32)
    return jnp.dtype(jnp.int64)


@dataclasses.dataclass(frozen=True)
class Quantized:
    """A value tensor narrowed for the wire, plus what is needed to undo it."""

    payload: Array  # narrow dtype, same shape as the original
    scale: Array | None  # per-block scales for int8 mode, else None
    mode: str  # "none" | "bf16" | "int8"

    def wire_bytes(self) -> int:
        n = int(np.prod(self.payload.shape)) * self.payload.dtype.itemsize
        if self.scale is not None:
            n += int(np.prod(self.scale.shape)) * self.scale.dtype.itemsize
        return n


def quantize(x: Array, mode: str, block: int = 256) -> Quantized:
    """Narrow ``x`` for the wire. ``mode`` in {"none", "bf16", "int8"}."""
    if mode == "none":
        return Quantized(x, None, "none")
    if mode == "bf16":
        return Quantized(x.astype(jnp.bfloat16), None, "bf16")
    if mode == "int8":
        flat = x.reshape(-1)
        pad = (-flat.shape[0]) % block
        flat = jnp.pad(flat, (0, pad))
        blocks = flat.reshape(-1, block)
        scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
        scale = jnp.maximum(scale, jnp.finfo(x.dtype).tiny)
        q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
        return Quantized(q, scale.astype(jnp.float32), "int8")
    raise ValueError(f"unknown quantization mode {mode!r}")


def dequantize(q: Quantized, like: Array) -> Array:
    if q.mode == "none":
        return q.payload
    if q.mode == "bf16":
        return q.payload.astype(like.dtype)
    blocks = q.payload.astype(jnp.float32) * q.scale
    flat = blocks.reshape(-1)[: int(np.prod(like.shape))]
    return flat.reshape(like.shape).astype(like.dtype)


def quantize_with_feedback(
    x: Array, residual: Array, mode: str, block: int = 256
) -> tuple[Quantized, Array]:
    """Quantize ``x + residual``; return (wire payload, new residual).

    Error feedback keeps iterative reductions (gradient descent, PageRank power
    iteration) unbiased: what this round's narrowing dropped is re-injected
    next round instead of being lost.
    """
    target = x + residual
    q = quantize(target, mode, block)
    recovered = dequantize(q, target)
    return q, target - recovered


# ---------------------------------------------------------------------------
# 2) Host-side reference of the paper's byte format (for analysis and tests)
# ---------------------------------------------------------------------------


def _varint_len(v: int) -> int:
    v = int(v)
    if v < 0:
        return 10  # protobuf semantics: negatives take the full 10 bytes
    n = 1
    while v >= 0x80:
        v >>= 7
        n += 1
    return n


def varint_encode(v: int) -> bytes:
    """LEB128 varint (shared by both formats below)."""
    v = int(v)
    if v < 0:
        v += 1 << 64
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def varint_decode(buf: bytes, pos: int) -> tuple[int, int]:
    shift, result = 0, 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            if result >= 1 << 63:
                result -= 1 << 64
            return result, pos
        shift += 7


def blaze_encode_pairs(keys: np.ndarray, vals: np.ndarray) -> bytes:
    """The paper's format: varints in fixed field order, NO tags/wire-types."""
    out = bytearray()
    for k, v in zip(keys.tolist(), vals.tolist()):
        out += varint_encode(k)
        out += varint_encode(v)
    return bytes(out)


def blaze_decode_pairs(buf: bytes, n: int) -> tuple[np.ndarray, np.ndarray]:
    keys, vals, pos = np.empty(n, np.int64), np.empty(n, np.int64), 0
    for i in range(n):
        keys[i], pos = varint_decode(buf, pos)
        vals[i], pos = varint_decode(buf, pos)
    return keys, vals


def protobuf_encode_pairs(keys: np.ndarray, vals: np.ndarray) -> bytes:
    """Protobuf-style encoding: each field prefixed by a (tag, wire-type) byte."""
    out = bytearray()
    for k, v in zip(keys.tolist(), vals.tolist()):
        out.append((1 << 3) | 0)  # field 1, varint
        out += varint_encode(k)
        out.append((2 << 3) | 0)  # field 2, varint
        out += varint_encode(v)
    return bytes(out)


def message_sizes(keys: np.ndarray, vals: np.ndarray) -> dict[str, int]:
    """Analytical byte counts reproducing the paper's §2.3.2 comparison."""
    blaze = sum(_varint_len(k) + _varint_len(v) for k, v in zip(keys, vals))
    proto = blaze + 2 * len(keys)  # one tag byte per field, two fields per pair
    return {"blaze_bytes": int(blaze), "protobuf_bytes": int(proto)}
