"""Jit'd public wrappers around the Pallas kernels.

Handles flattening, block padding, backend selection (interpret=True off
TPU so the kernel *body* is what gets validated on CPU), and exposes the
flat-array API the compressor layer consumes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import entropy as entropy_kernels
from repro.kernels import lorenzo

BLOCK = lorenzo.BLOCK
TILE_ROWS = lorenzo.TILE_ROWS
rows_per_step = lorenzo.rows_per_step


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def n_blocks_for(n: int) -> int:
    """Number of Lorenzo blocks (padded to the kernel's row-tile multiple)."""
    nb = -(-n // BLOCK)
    return -(-nb // TILE_ROWS) * TILE_ROWS


def to_blocks(x: jnp.ndarray) -> jnp.ndarray:
    """Flatten + zero-pad an arbitrary f32 array to (n_blocks, BLOCK)."""
    flat = x.reshape(-1).astype(jnp.float32)
    nb = n_blocks_for(flat.shape[0])
    padded = jnp.zeros((nb * BLOCK,), jnp.float32).at[: flat.shape[0]].set(flat)
    return padded.reshape(nb, BLOCK)


def from_blocks(x2d: jnp.ndarray, n: int) -> jnp.ndarray:
    return x2d.reshape(-1)[:n]


def quantize(x2d: jnp.ndarray, eb):
    """-> (codes uint32 (nb, B), bitwidth int32 (nb,), anchor int32 (nb,))."""
    eb = jnp.asarray(eb, jnp.float32)
    return lorenzo.quantize(x2d, eb, interpret=_interpret())


def dequantize(codes: jnp.ndarray, anchor: jnp.ndarray, eb) -> jnp.ndarray:
    eb = jnp.asarray(eb, jnp.float32)
    return lorenzo.dequantize(codes, anchor, eb, interpret=_interpret())


def dequantize_reduce(
    codes: jnp.ndarray, anchor: jnp.ndarray, eb, acc: jnp.ndarray
) -> jnp.ndarray:
    eb = jnp.asarray(eb, jnp.float32)
    return lorenzo.dequantize_reduce(codes, anchor, eb, acc, interpret=_interpret())


def quantize_pack(x2d: jnp.ndarray, eb, capacity_words: int):
    """Fused f32 -> packed wire words (single pallas_call, no codes array).

    -> (packed uint32 (capacity_words,), bw int32 (nb,), anchor int32 (nb,)).
    Byte-identical to ``bitpack.pack(*quantize(x2d, eb)[:2], capacity)``.
    """
    eb = jnp.asarray(eb, jnp.float32)
    return lorenzo.quantize_pack(
        x2d, eb, int(capacity_words), interpret=_interpret()
    )


def unpack_dequantize(
    packed: jnp.ndarray, bitwidth: jnp.ndarray, anchor: jnp.ndarray, eb
) -> jnp.ndarray:
    """Fused packed words -> decompressed f32 (nb, BLOCK), no accumulator."""
    eb = jnp.asarray(eb, jnp.float32)
    return lorenzo.unpack_dequantize(
        packed, bitwidth, anchor, eb, interpret=_interpret()
    )


def unpack_dequantize_reduce(
    packed: jnp.ndarray, bitwidth: jnp.ndarray, anchor: jnp.ndarray, eb,
    acc2d: jnp.ndarray,
) -> jnp.ndarray:
    """Fused packed words + acc -> acc + decompressed f32 (nb, BLOCK)."""
    eb = jnp.asarray(eb, jnp.float32)
    return lorenzo.unpack_dequantize_reduce(
        packed, bitwidth, anchor, eb, acc2d, interpret=_interpret()
    )


def entropy_quantize_pack(
    x2d: jnp.ndarray, eb, capacity_words: int, *, lossless: bool = False
):
    """Fused f32 -> entropy-coded wire words (DESIGN.md §10).

    -> (packed uint32 (capacity_words,), desc int32 (nb,), anchor int32
    (nb,)) where ``desc`` packs the four per-sub-block widths.  Byte-
    identical to ``core.entropy.pack(core.entropy.encode_blocks(...))``.
    """
    eb = jnp.asarray(eb, jnp.float32)
    return entropy_kernels.quantize_pack(
        x2d, eb, int(capacity_words), lossless=lossless, interpret=_interpret()
    )


def entropy_unpack_dequantize(
    packed: jnp.ndarray, desc: jnp.ndarray, anchor: jnp.ndarray, eb, *,
    lossless: bool = False,
) -> jnp.ndarray:
    """Fused entropy wire words -> decompressed f32 (nb, BLOCK)."""
    eb = jnp.asarray(eb, jnp.float32)
    return entropy_kernels.unpack_dequantize(
        packed, desc, anchor, eb, lossless=lossless, interpret=_interpret()
    )


def entropy_unpack_dequantize_reduce(
    packed: jnp.ndarray, desc: jnp.ndarray, anchor: jnp.ndarray, eb,
    acc2d: jnp.ndarray, *, lossless: bool = False,
) -> jnp.ndarray:
    """Fused entropy wire words + acc -> acc + decompressed f32 (nb, BLOCK)."""
    eb = jnp.asarray(eb, jnp.float32)
    return entropy_kernels.unpack_dequantize_reduce(
        packed, desc, anchor, eb, acc2d, lossless=lossless,
        interpret=_interpret(),
    )


def unpack_reduce_repack(
    packed: jnp.ndarray, bitwidth: jnp.ndarray, anchor: jnp.ndarray, eb_in,
    acc2d: jnp.ndarray, eb_out, capacity_words: int, *, emit_f32: bool = False,
):
    """Single-pass ring hop: received wire stream + local f32 chunk -> the
    next hop's wire stream (packed_out, bw_out, anchor_out[, updated f32]).

    Byte-identical to ``quantize_pack(unpack_dequantize_reduce(...))``; the
    f32 intermediate stays in VMEM unless ``emit_f32``.
    """
    eb_in = jnp.asarray(eb_in, jnp.float32)
    eb_out = jnp.asarray(eb_out, jnp.float32)
    return lorenzo.unpack_reduce_repack(
        packed, bitwidth, anchor, eb_in, acc2d, eb_out, int(capacity_words),
        emit_f32=emit_f32, interpret=_interpret(),
    )
