"""Pallas TPU kernels for the cuSZp-adapted block compressor.

Kernels, each tiled ``(TILE_ROWS, BLOCK)`` over a grid of block-rows:

  * ``quantize``          f32 -> zigzag codes + per-block bitwidth
  * ``dequantize``        codes -> f32 (per-block prefix-sum reconstruct)
  * ``dequantize_reduce`` codes + accumulator -> accumulator + f32
    (the paper's on-device reduction kernel, fused with decompression so the
    decompressed tensor never round-trips HBM)
  * ``quantize_pack``     f32 -> packed uint32 words directly (DESIGN.md §3):
    the full compression pipeline in ONE pass — the intermediate codes
    array never exists and the separate jnp bitpack scatter pass (with its
    global cumsum sync) is gone.
  * ``unpack_dequantize_reduce``  packed words + acc -> reduced f32, the
    exact inverse fusion for the receive side of a collective.
  * ``unpack_dequantize``  the accumulator-free variant for pure
    decompression (allgather/scatter receive paths).
  * ``unpack_reduce_repack``  the single-pass ring hop: received packed
    words + local f32 chunk -> the NEXT hop's packed words, in one pass —
    the updated f32 chunk never leaves VMEM (DESIGN.md §3.1).

Fused-pack layout invariant: BLOCK is a multiple of 32, so every block's
``BLOCK * bw_i`` bit payload is a whole number of uint32 words — block
boundaries are always word-aligned.  That is what makes single-pass
packing possible on a block-parallel grid: a tile of TILE_ROWS blocks
emits exactly ``8 * sum(bw)`` words at a word offset carried across the
sequential TPU grid in SMEM scratch (no global cumsum, no second pass).
The byte stream is IDENTICAL to ``bitpack.pack(quantize(x))`` — oracle-
tested in tests/test_fused_pipeline.py.

Wire stream in HBM (DESIGN.md §3.2).  The packed stream stays in HBM
(``memory_space=pl.ANY``), viewed as ``(lines, LANES)`` uint32 so that
every DMA moves whole 128-word lines.  A tile's segment starts at an
arbitrary word offset ``start``: the pack side assembles it in a
``(WIN_LINES, LANES)`` VMEM window at lane offset ``start % LANES``, ORs
in the carried partial line of the previous tile, and DMAs the window to
line ``start // LANES``; the unpack side DMAs the same window in.  Inside
the window each block row is moved to or from its word offset by a flat
roll (a dynamic sublane roll plus a dynamic lane roll), and the per-word
/ per-element bit shuffles are lane gathers within one 128-lane vreg.
No scatter, no resident capacity-sized block.

TPU tiling notes (DESIGN.md §2): BLOCK=256 keeps each Lorenzo block two
128-lane vregs wide; TILE_ROWS=8 gives an (8, 256) f32 tile = 8 KiB VMEM in,
8 KiB out, well under VMEM while a multiple of the (8, 128) f32 native tile.
The per-block cumsum is a log-step lane-roll prefix sum on the VPU; blocks
are independent so there is no cross-tile carry — this is what replaces
cuSZp's per-warp layout on the MXU-less part of the chip.

The scalar error bound arrives as a (1, 1) operand mapped to every grid
cell (index_map -> (0, 0)) rather than a closure constant, so one compiled
kernel serves every error budget the collective layer allocates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 256
TILE_ROWS = 8
LANES = 128
# A tile emits at most TILE_ROWS * BLOCK = 2048 words; placed at a lane
# offset of up to LANES - 1 that spans 17 lines.  24 keeps the window a
# whole number of (8, 128) vregs.
WIN_LINES = 24
_SIGN = 0x80000000


def _umax(u, axis):
    """Unsigned max: Mosaic reduces only signed integers, so flip the sign
    bit (an order-preserving map from uint32 onto int32) around the max."""
    s = jax.lax.bitcast_convert_type(u ^ jnp.uint32(_SIGN), jnp.int32)
    m = jnp.max(s, axis=axis, keepdims=True)
    return jax.lax.bitcast_convert_type(m, jnp.uint32) ^ jnp.uint32(_SIGN)


def _bitwidth(umax_col: jnp.ndarray) -> jnp.ndarray:
    """(R, 1) uint32 maxima -> (R, 1) int32 bits needed."""
    powers = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)).astype(jnp.uint32)
    return jnp.sum((umax_col >= powers[None, :]).astype(jnp.int32), axis=-1,
                   keepdims=True)


def _row_cumsum(d):
    """Inclusive prefix sum along lanes as log-step roll-and-add (Mosaic
    has no cumsum).  int32 addition wraps, so the result equals
    ``jnp.cumsum`` bit for bit."""
    lane = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)

    def step(i, d):
        s = jnp.left_shift(jnp.int32(1), i)
        return d + jnp.where(lane >= s, pltpu.roll(d, s, 1), 0)

    return jax.lax.fori_loop(0, d.shape[1].bit_length() - 1, step, d)


def _zigzag_tile(q):
    """int32 tile -> (zigzag Lorenzo deltas, anchor col)."""
    col = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1)
    prev = jnp.where(col == 0, q, pltpu.roll(q, 1, 1))
    d = q - prev  # first column is 0; absolute value goes out via anchor
    return ((d << 1) ^ (d >> 31)).astype(jnp.uint32), q[:, :1]


def _quantize_tile(x, recip):
    """Shared quantization math: f32 tile -> (zigzag codes, bw col, anchor col)."""
    zig, anchor = _zigzag_tile(jnp.rint(x * recip).astype(jnp.int32))
    return zig, _bitwidth(_umax(zig, 1)), anchor


def _unzigzag_cumsum(u, anchor_col):
    d = (u >> 1).astype(jnp.int32) ^ (-(u & 1).astype(jnp.int32))
    return anchor_col + _row_cumsum(d)


def _reconstruct(u, anchor_col, twoeb):
    return _unzigzag_cumsum(u, anchor_col).astype(jnp.float32) * twoeb


def _quantize_kernel(x_ref, recip_ref, codes_ref, bw_ref, anchor_ref):
    zig, bw, anchor = _quantize_tile(x_ref[...], recip_ref[0, 0])
    codes_ref[...] = zig
    bw_ref[...] = bw
    anchor_ref[...] = anchor


def _dequantize_kernel(codes_ref, anchor_ref, twoeb_ref, x_ref):
    x_ref[...] = _reconstruct(codes_ref[...], anchor_ref[...], twoeb_ref[0, 0])


def _dequantize_reduce_kernel(codes_ref, anchor_ref, twoeb_ref, acc_ref, out_ref):
    out_ref[...] = acc_ref[...] + _reconstruct(
        codes_ref[...], anchor_ref[...], twoeb_ref[0, 0]
    )


def _scalar_spec():
    return pl.BlockSpec((1, 1), lambda i: (0, 0))


def _row_spec(width):
    return pl.BlockSpec((TILE_ROWS, width), lambda i: (i, 0))


_ANY = pl.BlockSpec(memory_space=pl.ANY)
# The wire-stream carries walk the grid in order.
_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize(x2d: jnp.ndarray, eb: jnp.ndarray, *, interpret: bool = True):
    """f32 (n_blocks, BLOCK) -> (codes uint32, bitwidth int32 (n_blocks,)).

    n_blocks must be a multiple of TILE_ROWS (ops.py pads).
    """
    n_blocks = x2d.shape[0]
    recip = (1.0 / (2.0 * eb)).reshape(1, 1).astype(jnp.float32)
    grid = (n_blocks // TILE_ROWS,)
    codes, bw, anchor = pl.pallas_call(
        _quantize_kernel,
        grid=grid,
        in_specs=[_row_spec(BLOCK), _scalar_spec()],
        out_specs=[_row_spec(BLOCK), _row_spec(1), _row_spec(1)],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, BLOCK), jnp.uint32),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
        ],
        interpret=interpret,
    )(x2d, recip)
    return codes, bw[:, 0], anchor[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize(
    codes: jnp.ndarray, anchor: jnp.ndarray, eb: jnp.ndarray, *, interpret: bool = True
):
    """codes uint32 (n_blocks, BLOCK) + anchor (n_blocks,) -> f32 (n_blocks, BLOCK)."""
    n_blocks = codes.shape[0]
    twoeb = (2.0 * eb).reshape(1, 1).astype(jnp.float32)
    return pl.pallas_call(
        _dequantize_kernel,
        grid=(n_blocks // TILE_ROWS,),
        in_specs=[_row_spec(BLOCK), _row_spec(1), _scalar_spec()],
        out_specs=_row_spec(BLOCK),
        out_shape=jax.ShapeDtypeStruct((n_blocks, BLOCK), jnp.float32),
        interpret=interpret,
    )(codes, anchor[:, None], twoeb)


# ---------------------------------------------------------------------------
# Fused compression pipeline (DESIGN.md §3)
#
# The helpers below serve both wire formats.  A block row splits into
# ``len(sub_bw)`` equal sub-blocks, sub ``k`` packed at width
# ``sub_bw[k]`` (a (TILE_ROWS, 1) int32 column); the dense format is the
# one-sub case, the entropy format (kernels/entropy.py) the four-sub one.
# ---------------------------------------------------------------------------


def _width_mask(bw):
    """int32 widths -> uint32 masks of that many low bits (integer min:
    Mosaic has no unsigned min)."""
    return jnp.where(
        bw == 0,
        jnp.uint32(0),
        jnp.uint32(0xFFFFFFFF) >> jnp.minimum(32 - bw, 31).astype(jnp.uint32),
    )


def _sub_geometry(sub_bw):
    """-> (sub size, words per bit of width, word offset col of each sub)."""
    sub = BLOCK // len(sub_bw)
    wpb = sub // 32
    offs, acc = [], jnp.zeros_like(sub_bw[0])
    for b in sub_bw:
        offs.append(acc)
        acc = acc + wpb * b
    return sub, wpb, offs


def _block_words(sub_bw):
    """(TILE_ROWS, 1) int32 packed words of each block row."""
    sub, wpb, offs = _sub_geometry(sub_bw)
    return offs[-1] + wpb * sub_bw[-1]


def _take(lo, hi, idx):
    """Lane gather ``row[idx]`` from a 256-lane row held as two 128-lane
    halves; Mosaic gathers within one vreg only."""
    def half(ix):
        k = ix & (LANES - 1)
        g_lo = jnp.take_along_axis(lo, k, axis=1, mode="promise_in_bounds")
        g_hi = jnp.take_along_axis(hi, k, axis=1, mode="promise_in_bounds")
        return jnp.where(ix < LANES, g_lo, g_hi)

    return jnp.concatenate([half(idx[:, :LANES]), half(idx[:, LANES:])], axis=1)


def _select_sub(sub_bw, offs, sub, lane):
    """Per-lane (width, word offset) of the sub-block each element lane is in."""
    b = jnp.zeros(lane.shape, jnp.int32)
    off = jnp.zeros(lane.shape, jnp.int32)
    for k, (bk, ok) in enumerate(zip(sub_bw, offs)):
        m = (lane >= k * sub) & (lane < (k + 1) * sub)
        b = jnp.where(m, bk, b)
        off = jnp.where(m, ok, off)
    return b, off


def _pack_rows(u, sub_bw):
    """(TILE_ROWS, BLOCK) codes -> (TILE_ROWS, BLOCK) words: row r holds
    block r's packed words in lanes [0, words_r), zeros after.  Every
    code must fit its sub-block's width (the widths come from the codes'
    own maxima).

    Word ``k`` of a sub at width ``b`` gathers the elements overlapping
    its 32 bits — element ``floor(32 k / b) + m`` for ``m`` up to
    ``ceil(32 / b) + 1`` — so the loop runs as many rounds as the
    narrowest non-zero width in the tile needs.
    """
    sub, wpb, offs = _sub_geometry(sub_bw)
    lane = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    # Per output word: width, first element of its sub, sub-local word.
    b = jnp.zeros(u.shape, jnp.int32)
    base = jnp.zeros(u.shape, jnp.int32)
    kk = lane
    valid = jnp.zeros(u.shape, jnp.bool_)
    for k, (bk, ok) in enumerate(zip(sub_bw, offs)):
        m = (lane >= ok) & (lane < ok + wpb * bk)
        b = jnp.where(m, bk, b)
        base = jnp.where(m, k * sub, base)
        kk = jnp.where(m, lane - ok, kk)
        valid = valid | m
    j0 = (32 * kk) // jnp.maximum(b, 1)
    rounds = jnp.zeros_like(sub_bw[0])
    for bk in sub_bw:
        rounds = jnp.maximum(
            rounds, jnp.where(bk > 0, (31 + bk) // jnp.maximum(bk, 1) + 1, 0))
    lo, hi = u[:, :LANES], u[:, LANES:]

    def body(m, acc):
        jrel = j0 + m
        sh = jrel * b - 32 * kk  # element's bit offset relative to the word
        g = _take(lo, hi, jnp.minimum(base + jrel, BLOCK - 1))
        left = g << jnp.clip(sh, 0, 31).astype(jnp.uint32)
        right = g >> jnp.clip(-sh, 0, 31).astype(jnp.uint32)
        part = jnp.where(sh >= 0, left, right)
        return acc | jnp.where(valid & (sh < 32), part, jnp.uint32(0))

    return jax.lax.fori_loop(0, jnp.max(rounds), body, jnp.zeros_like(u))


def _unpack_rows(w, sub_bw):
    """Inverse of :func:`_pack_rows`: block-aligned word rows -> codes."""
    sub, wpb, offs = _sub_geometry(sub_bw)
    lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    b, off = _select_sub(sub_bw, offs, sub, lane)
    bitpos = off * 32 + (lane % sub) * b
    word = bitpos >> 5
    shift = bitpos & 31
    lo, hi = w[:, :LANES], w[:, LANES:]
    first = _take(lo, hi, word) >> shift.astype(jnp.uint32)
    second = _take(lo, hi, jnp.minimum(word + 1, BLOCK - 1))
    straddle = jnp.where(shift == 0, jnp.uint32(0),
                         second << (32 - jnp.maximum(shift, 1)).astype(jnp.uint32))
    return (first | straddle) & _width_mask(b)


def _words_of_row(words_col, r):
    """Scalar words of block row ``r`` (traced) of a (TILE_ROWS, 1) column."""
    row = jax.lax.broadcasted_iota(jnp.int32, words_col.shape, 0)
    return jnp.sum(jnp.where(row == r, words_col, 0))


def _flat_roll(x, s):
    """Roll a (lines, LANES) window by ``s`` words toward higher flat
    indices (``s`` a non-negative scalar below the window size)."""
    a, c = s // LANES, s % LANES
    z = pltpu.roll(pltpu.roll(x, a, 0), c, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane >= c, z, pltpu.roll(z, 1, 0))


def _window_start(start, hbm_ref):
    """Clamp a tile's word offset into the stream: a tile past the
    provisioned capacity reads/writes the WIN_LINES dump tail, never a
    valid word."""
    cap_lines = hbm_ref.shape[0] - WIN_LINES
    s = jnp.minimum(start, cap_lines * LANES)
    return s // LANES, s % LANES


def _emit_tile(words, words_col, hbm_ref, off_ref, carry_ref, seg_ref, sem):
    """Append one tile's packed rows to the HBM wire stream.

    Rows are placed back to back at the carried word offset inside a
    window that starts on the offset's line; line 0 is ORed with the
    previous tile's partial last line (blocks are word-aligned, so OR ==
    concatenation).  The window DMA waits for the previous one: they
    overlap on that line, and the window buffer is reused.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        off_ref[0] = 0
        carry_ref[...] = jnp.zeros_like(carry_ref)

    start = off_ref[0]
    line0, b0 = _window_start(start, hbm_ref)
    line = jax.lax.broadcasted_iota(jnp.int32, seg_ref.shape, 0)
    lo, hi = words[:, :LANES], words[:, LANES:]

    def place(r, carry):  # row r's two lines, rolled to its word offset
        seg, off = carry
        pick = lambda half: pltpu.roll(half, (TILE_ROWS - r) % TILE_ROWS, 0)[:1]
        placed = jnp.where(line == 0, pick(lo),
                           jnp.where(line == 1, pick(hi), jnp.uint32(0)))
        return (seg | _flat_roll(placed, b0 + off),
                off + _words_of_row(words_col, r))

    seg, total = jax.lax.fori_loop(
        0, TILE_ROWS, place,
        (jnp.where(line == 0, carry_ref[...], jnp.uint32(0)), jnp.int32(0)))
    end = (b0 + total) // LANES
    carry_ref[...] = pltpu.roll(seg, (WIN_LINES - end) % WIN_LINES, 0)[:1]
    copy = pltpu.make_async_copy(
        seg_ref, hbm_ref.at[pl.ds(line0, WIN_LINES)], sem)

    @pl.when(i > 0)
    def _():
        copy.wait()  # the previous tile's window (same shape and semaphore)

    seg_ref[...] = seg
    copy.start()

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        copy.wait()

    off_ref[0] = start + total


def _fetch_tile(words_col, hbm_ref, off_ref, win_ref, sem):
    """Read one tile's block rows from the HBM wire stream, each moved to
    lane 0 of its row: -> (TILE_ROWS, BLOCK) words for ``_unpack_rows``."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        off_ref[0] = 0

    start = off_ref[0]
    line0, b0 = _window_start(start, hbm_ref)
    copy = pltpu.make_async_copy(
        hbm_ref.at[pl.ds(line0, WIN_LINES)], win_ref, sem)
    copy.start()
    copy.wait()
    win = win_ref[...]
    size = WIN_LINES * LANES
    row = jax.lax.broadcasted_iota(jnp.int32, (TILE_ROWS, LANES), 0)

    def take(r, carry):  # the window rolled back to row r's word offset
        lo, hi, off = carry
        y = _flat_roll(win, (size - b0 - off) % size)
        return (jnp.where(row == r, y[0:1], lo), jnp.where(row == r, y[1:2], hi),
                off + _words_of_row(words_col, r))

    zeros = jnp.zeros((TILE_ROWS, LANES), jnp.uint32)
    lo, hi, total = jax.lax.fori_loop(0, TILE_ROWS, take,
                                      (zeros, zeros, jnp.int32(0)))
    off_ref[0] = start + total
    return jnp.concatenate([lo, hi], axis=1)


def _pack_scratch():
    """off carry, partial-line carry, window buffer, DMA semaphore."""
    return [
        pltpu.SMEM((1,), jnp.int32),
        pltpu.VMEM((1, LANES), jnp.uint32),
        pltpu.VMEM((WIN_LINES, LANES), jnp.uint32),
        pltpu.SemaphoreType.DMA(()),
    ]


def _unpack_scratch():
    """off carry, window buffer, DMA semaphore."""
    return [
        pltpu.SMEM((1,), jnp.int32),
        pltpu.VMEM((WIN_LINES, LANES), jnp.uint32),
        pltpu.SemaphoreType.DMA(()),
    ]


def stream_lines(capacity_words: int) -> int:
    """Lines of the (lines, LANES) HBM view of a stream of this capacity,
    including the WIN_LINES dump tail."""
    return -(-capacity_words // LANES) + WIN_LINES


def to_lines(packed: jnp.ndarray) -> jnp.ndarray:
    """Flat wire stream -> zero-padded (lines, LANES) view for the kernels."""
    n = packed.shape[0]
    return jnp.pad(packed, (0, stream_lines(n) * LANES - n)).reshape(-1, LANES)


def _quantize_pack_kernel(x_ref, recip_ref, _zeros, packed_ref, bw_ref,
                          anchor_ref, *scratch):
    """quantize + zigzag + bitpack in one pass over the tile."""
    zig, bw, anchor = _quantize_tile(x_ref[...], recip_ref[0, 0])
    bw_ref[...] = bw
    anchor_ref[...] = anchor
    _emit_tile(_pack_rows(zig, [bw]), _block_words([bw]), packed_ref,
               *scratch)


def _unpack_dequantize_reduce_kernel(packed_ref, bw_ref, anchor_ref, twoeb_ref,
                                     acc_ref, out_ref, *scratch):
    """Inverse fusion: packed words + acc -> acc + dequantize(unpack(words)).

    Same SMEM word-offset carry as the pack kernel; the tile DMAs its
    word window from the HBM stream, so the uint32 codes array never
    materializes in HBM on the receive side either.
    """
    bw = bw_ref[...]
    w = _fetch_tile(_block_words([bw]), packed_ref, *scratch)
    out_ref[...] = acc_ref[...] + _reconstruct(
        _unpack_rows(w, [bw]), anchor_ref[...], twoeb_ref[0, 0])


def _unpack_dequantize_kernel(packed_ref, bw_ref, anchor_ref, twoeb_ref,
                              out_ref, *scratch):
    """Pure fused decompress (no accumulator): the allgather/scatter receive
    path, which would otherwise pay a zero-accumulator materialization."""
    bw = bw_ref[...]
    w = _fetch_tile(_block_words([bw]), packed_ref, *scratch)
    out_ref[...] = _reconstruct(_unpack_rows(w, [bw]), anchor_ref[...],
                                twoeb_ref[0, 0])


def _unpack_reduce_repack_kernel(emit_f32, packed_in_ref, bw_in_ref,
                                 anchor_in_ref, twoeb_ref, acc_ref, recip_ref,
                                 _zeros, *refs):
    """The single-pass ring hop (DESIGN.md §3.1): per tile, fetch the
    received packed segment, unpack + un-zigzag + prefix-sum + dequantize,
    add the local accumulator chunk, then immediately re-quantize, zigzag
    and pack the updated chunk onto the outgoing wire stream.  The f32
    intermediate lives only in VMEM (unless ``emit_f32`` — the redoub
    carry needs it); the outgoing per-block bitwidths/anchors come out of
    the same pass.  Two SMEM word-offset carries: one walking the
    received stream, one walking the outgoing stream.
    """
    n_out = 4 if emit_f32 else 3
    outs, scratch = refs[:n_out], refs[n_out:]
    packed_out_ref, bw_out_ref, anchor_out_ref = outs[:3]
    bw_in = bw_in_ref[...]
    w = _fetch_tile(_block_words([bw_in]), packed_in_ref, *scratch[4:])
    x = acc_ref[...] + _reconstruct(_unpack_rows(w, [bw_in]),
                                    anchor_in_ref[...], twoeb_ref[0, 0])
    zig, bw, anchor = _quantize_tile(x, recip_ref[0, 0])
    bw_out_ref[...] = bw
    anchor_out_ref[...] = anchor
    if emit_f32:
        outs[3][...] = x
    _emit_tile(_pack_rows(zig, [bw]), _block_words([bw]), packed_out_ref,
               *scratch[:4])


@functools.partial(
    jax.jit, static_argnames=("capacity_words", "emit_f32", "interpret")
)
def unpack_reduce_repack(
    packed: jnp.ndarray,
    bitwidth: jnp.ndarray,
    anchor: jnp.ndarray,
    eb_in: jnp.ndarray,
    acc: jnp.ndarray,
    eb_out: jnp.ndarray,
    capacity_words: int,
    *,
    emit_f32: bool = False,
    interpret: bool = True,
):
    """Fused unpack + dequantize + reduce + re-quantize + re-pack.

    One ``pallas_call`` per ring hop: consumes the received wire stream
    (``packed``/``bitwidth``/``anchor`` at ``eb_in``) plus the local f32
    chunk ``acc`` (n_blocks, BLOCK), and emits the *next hop's* wire stream
    at ``eb_out`` — byte-identical to
    ``quantize_pack(unpack_dequantize_reduce(...))`` without the f32
    intermediate ever leaving VMEM.  With ``emit_f32`` the updated f32
    chunk is also written out (the recursive-doubling carry).

    Returns (packed_out uint32[capacity_words], bw_out, anchor_out[,
    updated f32 (n_blocks, BLOCK)]).
    """
    n_blocks = acc.shape[0]
    twoeb = (2.0 * eb_in).reshape(1, 1).astype(jnp.float32)
    recip = (1.0 / (2.0 * eb_out)).reshape(1, 1).astype(jnp.float32)
    out_lines = stream_lines(capacity_words)
    out_specs = [_ANY, _row_spec(1), _row_spec(1)]
    out_shape = [
        jax.ShapeDtypeStruct((out_lines, LANES), jnp.uint32),
        jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
        jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
    ]
    if emit_f32:
        out_specs.append(_row_spec(BLOCK))
        out_shape.append(jax.ShapeDtypeStruct((n_blocks, BLOCK), jnp.float32))
    res = pl.pallas_call(
        functools.partial(_unpack_reduce_repack_kernel, emit_f32),
        grid=(n_blocks // TILE_ROWS,),
        in_specs=[
            _ANY,
            _row_spec(1),
            _row_spec(1),
            _scalar_spec(),
            _row_spec(BLOCK),
            _scalar_spec(),
            _ANY,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=_pack_scratch() + _unpack_scratch(),
        input_output_aliases={6: 0},
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(to_lines(packed), bitwidth[:, None], anchor[:, None], twoeb, acc, recip,
      jnp.zeros((out_lines, LANES), jnp.uint32))
    packed_out = res[0].reshape(-1)[:capacity_words]
    if emit_f32:
        return packed_out, res[1][:, 0], res[2][:, 0], res[3]
    return packed_out, res[1][:, 0], res[2][:, 0]


@functools.partial(jax.jit, static_argnames=("capacity_words", "interpret"))
def quantize_pack(
    x2d: jnp.ndarray, eb: jnp.ndarray, capacity_words: int, *,
    interpret: bool = True,
):
    """f32 (n_blocks, BLOCK) -> (packed uint32[capacity_words], bw, anchor).

    Single pallas_call; byte stream identical to
    ``bitpack.pack(*quantize(x2d, eb))`` on the first capacity_words words.
    n_blocks must be a multiple of TILE_ROWS (ops.py pads).
    """
    n_blocks = x2d.shape[0]
    recip = (1.0 / (2.0 * eb)).reshape(1, 1).astype(jnp.float32)
    lines = stream_lines(capacity_words)
    packed, bw, anchor = pl.pallas_call(
        _quantize_pack_kernel,
        grid=(n_blocks // TILE_ROWS,),
        in_specs=[_row_spec(BLOCK), _scalar_spec(), _ANY],
        out_specs=[_ANY, _row_spec(1), _row_spec(1)],
        out_shape=[
            jax.ShapeDtypeStruct((lines, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
        ],
        scratch_shapes=_pack_scratch(),
        input_output_aliases={2: 0},
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(x2d, recip, jnp.zeros((lines, LANES), jnp.uint32))
    return packed.reshape(-1)[:capacity_words], bw[:, 0], anchor[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def unpack_dequantize(
    packed: jnp.ndarray,
    bitwidth: jnp.ndarray,
    anchor: jnp.ndarray,
    eb: jnp.ndarray,
    *,
    interpret: bool = True,
):
    """Fused unpack + dequantize: packed stream -> f32 (n_blocks, BLOCK)."""
    n_blocks = bitwidth.shape[0]
    twoeb = (2.0 * eb).reshape(1, 1).astype(jnp.float32)
    return pl.pallas_call(
        _unpack_dequantize_kernel,
        grid=(n_blocks // TILE_ROWS,),
        in_specs=[_ANY, _row_spec(1), _row_spec(1), _scalar_spec()],
        out_specs=_row_spec(BLOCK),
        out_shape=jax.ShapeDtypeStruct((n_blocks, BLOCK), jnp.float32),
        scratch_shapes=_unpack_scratch(),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(to_lines(packed), bitwidth[:, None], anchor[:, None], twoeb)


@functools.partial(jax.jit, static_argnames=("interpret",))
def unpack_dequantize_reduce(
    packed: jnp.ndarray,
    bitwidth: jnp.ndarray,
    anchor: jnp.ndarray,
    eb: jnp.ndarray,
    acc: jnp.ndarray,
    *,
    interpret: bool = True,
):
    """Fused unpack + dequantize + reduce: acc + decompress(packed stream).

    ``packed``: uint32[capacity_words]; ``acc``: f32 (n_blocks, BLOCK).
    """
    n_blocks = acc.shape[0]
    twoeb = (2.0 * eb).reshape(1, 1).astype(jnp.float32)
    return pl.pallas_call(
        _unpack_dequantize_reduce_kernel,
        grid=(n_blocks // TILE_ROWS,),
        in_specs=[_ANY, _row_spec(1), _row_spec(1), _scalar_spec(),
                  _row_spec(BLOCK)],
        out_specs=_row_spec(BLOCK),
        out_shape=jax.ShapeDtypeStruct((n_blocks, BLOCK), jnp.float32),
        scratch_shapes=_unpack_scratch(),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(to_lines(packed), bitwidth[:, None], anchor[:, None], twoeb, acc)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize_reduce(
    codes: jnp.ndarray,
    anchor: jnp.ndarray,
    eb: jnp.ndarray,
    acc: jnp.ndarray,
    *,
    interpret: bool = True,
):
    """Fused decompress-and-add: acc + dequantize(codes, anchor)."""
    n_blocks = codes.shape[0]
    twoeb = (2.0 * eb).reshape(1, 1).astype(jnp.float32)
    return pl.pallas_call(
        _dequantize_reduce_kernel,
        grid=(n_blocks // TILE_ROWS,),
        in_specs=[_row_spec(BLOCK), _row_spec(1), _scalar_spec(), _row_spec(BLOCK)],
        out_specs=_row_spec(BLOCK),
        out_shape=jax.ShapeDtypeStruct((n_blocks, BLOCK), jnp.float32),
        interpret=interpret,
    )(codes, anchor[:, None], twoeb, acc)
