"""Pallas TPU kernels for the entropy-coded wire stage (DESIGN.md §10).

Same single-pass structure as the dense kernels in ``lorenzo.py`` —
``rows_per_step(n_blocks)`` block rows per grid step, the HBM wire stream
moved in one line window per step by DMA, and an SMEM word-offset carry
across the sequential grid — but each block's payload is packed at FOUR per-sub-block widths
instead of one: block ``i`` splits into ``SUBS`` sub-blocks of ``SUB``
elements and sub ``k`` occupies exactly ``SUB // 32 * bw_k`` words (SUB
is a multiple of 32, so sub boundaries stay word-aligned and the dense
packer's alignment argument carries over unchanged).  The row packer and
unpacker are the dense kernels' own, called with four width columns.

The four 6-bit sub-widths travel packed into one int32 descriptor in the
``Compressed.bitwidth`` slot, so a row's worst case is still ``BLOCK``
words and the dense kernels' window and dump-tail overflow clamp apply
verbatim.

A static ``lossless`` flag swaps the error-bounded quantizer for a
bit-exact ``bitcast(f32)->int32`` front end; everything downstream
(delta, zigzag, entropy pack) is shared, and int32 wraparound makes the
delta chain reconstruct exactly.

Byte streams are IDENTICAL to the jnp oracle in ``core/entropy.py``
(asserted in tests/test_codecs.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lorenzo import (
    BLOCK,
    _ANY,
    _SEQUENTIAL,
    _bitwidth,
    _block_words,
    _emit_step,
    _fetch_step,
    _pack_scratch,
    _row_spec,
    _scalar_spec,
    _umax,
    _unpack_rows,
    _unpack_scratch,
    _unzigzag_cumsum,
    _zigzag_tile,
    LANES,
    rows_per_step,
    stream_lines,
    to_lines,
)

SUBS = 4
SUB = BLOCK // SUBS
_DESC_BITS = 6


def _codes_tile(x, recip, lossless):
    """f32 tile -> (zigzag codes, anchor col); lossless bitcasts instead of
    quantizing so the delta chain acts on raw IEEE bit patterns."""
    if lossless:
        q = jax.lax.bitcast_convert_type(x, jnp.int32)
    else:
        q = jnp.rint(x * recip).astype(jnp.int32)
    return _zigzag_tile(q)


def _sub_widths_tile(zig):
    """(rows, BLOCK) zigzag codes -> SUBS (rows, 1) int32 width columns.
    Masked per-sub maxima via a static unroll — no reshape of the lane
    dimension, no gather."""
    sub_idx = jax.lax.broadcasted_iota(jnp.int32, zig.shape, 1) // SUB
    return [_bitwidth(_umax(jnp.where(sub_idx == k, zig, jnp.uint32(0)), 1))
            for k in range(SUBS)]


def _make_desc_col(sub_bw):
    desc = sub_bw[0]
    for k in range(1, SUBS):
        desc = desc | (sub_bw[k] << (_DESC_BITS * k))
    return desc


def _split_desc_col(desc_col):
    mask = (1 << _DESC_BITS) - 1
    return [(desc_col >> (_DESC_BITS * k)) & mask for k in range(SUBS)]


def _reconstruct(u, anchor_col, twoeb, lossless):
    q = _unzigzag_cumsum(u, anchor_col)
    if lossless:
        return jax.lax.bitcast_convert_type(q, jnp.float32)
    return q.astype(jnp.float32) * twoeb


def _quantize_pack_kernel(lossless, unroll, x_ref, recip_ref, _zeros,
                          packed_ref, desc_ref, anchor_ref, *scratch):
    """quantize (or bitcast) + zigzag + entropy pack in one pass."""
    zig, anchor = _codes_tile(x_ref[...], recip_ref[0, 0], lossless)
    sub_bw = _sub_widths_tile(zig)
    desc_ref[...] = _make_desc_col(sub_bw)
    anchor_ref[...] = anchor
    _emit_step(unroll, zig, lambda s: _split_desc_col(desc_ref[s, :]),
               _block_words(sub_bw), packed_ref, *scratch)


def _unpack_codes(unroll, packed_ref, desc_ref, scratch):
    sub_bw = _split_desc_col(desc_ref[...])
    w = _fetch_step(unroll, _block_words(sub_bw), packed_ref, *scratch)
    return _unpack_rows(w, sub_bw)


def _unpack_dequantize_kernel(lossless, unroll, packed_ref, desc_ref,
                              anchor_ref, twoeb_ref, out_ref, *scratch):
    u = _unpack_codes(unroll, packed_ref, desc_ref, scratch)
    out_ref[...] = _reconstruct(u, anchor_ref[...], twoeb_ref[0, 0], lossless)


def _unpack_dequantize_reduce_kernel(lossless, unroll, packed_ref, desc_ref,
                                     anchor_ref, twoeb_ref, acc_ref, out_ref,
                                     *scratch):
    u = _unpack_codes(unroll, packed_ref, desc_ref, scratch)
    out_ref[...] = acc_ref[...] + _reconstruct(
        u, anchor_ref[...], twoeb_ref[0, 0], lossless
    )


def _eb_scalars(eb, lossless):
    """(recip, twoeb) (1,1) f32 operands; inert ones in lossless mode so an
    eb of zero can't divide by zero on a path that never reads it."""
    if lossless:
        one = jnp.ones((1, 1), jnp.float32)
        return one, one
    recip = (1.0 / (2.0 * eb)).reshape(1, 1).astype(jnp.float32)
    twoeb = (2.0 * eb).reshape(1, 1).astype(jnp.float32)
    return recip, twoeb


@functools.partial(
    jax.jit, static_argnames=("capacity_words", "lossless", "interpret")
)
def quantize_pack(
    x2d: jnp.ndarray, eb: jnp.ndarray, capacity_words: int, *,
    lossless: bool = False, interpret: bool = True,
):
    """f32 (n_blocks, BLOCK) -> (packed uint32[capacity_words], desc int32
    (n_blocks,), anchor int32 (n_blocks,)) at per-sub-block widths.

    Byte stream identical to ``core.entropy.pack(encode_blocks(x2d, eb))``.
    """
    n_blocks = x2d.shape[0]
    rows = rows_per_step(n_blocks)
    recip, _ = _eb_scalars(eb, lossless)
    lines = stream_lines(capacity_words, rows)
    col = _row_spec(1, rows)
    packed, desc, anchor = pl.pallas_call(
        functools.partial(_quantize_pack_kernel, lossless, not interpret),
        grid=(n_blocks // rows,),
        in_specs=[_row_spec(BLOCK, rows), _scalar_spec(), _ANY],
        out_specs=[_ANY, col, col],
        out_shape=[
            jax.ShapeDtypeStruct((lines, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
        ],
        scratch_shapes=_pack_scratch(rows),
        input_output_aliases={2: 0},
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(x2d, recip, jnp.zeros((lines, LANES), jnp.uint32))
    return packed.reshape(-1)[:capacity_words], desc[:, 0], anchor[:, 0]


@functools.partial(jax.jit, static_argnames=("lossless", "interpret"))
def unpack_dequantize(
    packed: jnp.ndarray, desc: jnp.ndarray, anchor: jnp.ndarray,
    eb: jnp.ndarray, *, lossless: bool = False, interpret: bool = True,
):
    """Entropy stream -> f32 (n_blocks, BLOCK), no accumulator."""
    n_blocks = desc.shape[0]
    rows = rows_per_step(n_blocks)
    _, twoeb = _eb_scalars(eb, lossless)
    col = _row_spec(1, rows)
    return pl.pallas_call(
        functools.partial(_unpack_dequantize_kernel, lossless, not interpret),
        grid=(n_blocks // rows,),
        in_specs=[_ANY, col, col, _scalar_spec()],
        out_specs=_row_spec(BLOCK, rows),
        out_shape=jax.ShapeDtypeStruct((n_blocks, BLOCK), jnp.float32),
        scratch_shapes=_unpack_scratch(rows),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(to_lines(packed, rows), desc[:, None], anchor[:, None], twoeb)


@functools.partial(jax.jit, static_argnames=("lossless", "interpret"))
def unpack_dequantize_reduce(
    packed: jnp.ndarray, desc: jnp.ndarray, anchor: jnp.ndarray,
    eb: jnp.ndarray, acc: jnp.ndarray, *,
    lossless: bool = False, interpret: bool = True,
):
    """Entropy stream + acc -> acc + decompressed f32 (n_blocks, BLOCK)."""
    n_blocks = acc.shape[0]
    rows = rows_per_step(n_blocks)
    _, twoeb = _eb_scalars(eb, lossless)
    col = _row_spec(1, rows)
    return pl.pallas_call(
        functools.partial(_unpack_dequantize_reduce_kernel, lossless,
                          not interpret),
        grid=(n_blocks // rows,),
        in_specs=[_ANY, col, col, _scalar_spec(), _row_spec(BLOCK, rows)],
        out_specs=_row_spec(BLOCK, rows),
        out_shape=jax.ShapeDtypeStruct((n_blocks, BLOCK), jnp.float32),
        scratch_shapes=_unpack_scratch(rows),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(to_lines(packed, rows), desc[:, None], anchor[:, None], twoeb, acc)
