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
packing possible on a block-parallel grid: a grid step's blocks emit
exactly ``8 * sum(bw)`` words at a word offset carried across the
sequential TPU grid in SMEM scratch (no global cumsum, no second pass).
The byte stream is IDENTICAL to ``bitpack.pack(quantize(x))`` — oracle-
tested in tests/test_fused_pipeline.py.

Wire stream in HBM (DESIGN.md §3.2).  The packed stream stays in HBM
(``memory_space=pl.ANY``), viewed as ``(lines, LANES)`` uint32 so that
every DMA moves whole 128-word lines.  The stream kernels walk it once
per grid step of ``rows_per_step(n_blocks)`` block rows (up to 128; the
unpacked kernels keep TILE_ROWS).  A step's segment starts at an
arbitrary word offset ``start``: the step's row offsets are one
exclusive prefix sum of its words column, moved to SMEM once; the pack
side rotates each row by its lane offset and ORs it, a tile of rows at a
time, into the (up to) 3 lines it covers of a ``(win_lines(rows),
LANES)`` VMEM window (line 0 holds the carried partial line of the
previous step), and DMAs the window to line ``start // LANES``; the
unpack side DMAs the same window in and rotates each row's lines back
to lane 0.  A row's cost does not depend on the
window's size.  The per-word / per-element bit shuffles are lane
gathers within one 128-lane vreg.  No scatter, no resident
capacity-sized block.

TPU tiling notes (DESIGN.md §2): BLOCK=256 keeps each Lorenzo block two
128-lane vregs wide; TILE_ROWS=8 gives an (8, 256) f32 tile = 8 KiB VMEM in,
8 KiB out, a multiple of the (8, 128) f32 native tile; it stays the padding
unit, and a stream kernel's step of up to 16 such tiles is 128 KiB.
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
# Tiles per grid step of the wire-stream kernels, at most.
MAX_TILES_PER_STEP = 16
# Lines a tile's packed rows are assembled in: TILE_ROWS rows of up to 2
# lines behind a lane offset span 17, from a line up to 7 past an aligned
# one.
_GROUP_LINES = 24
_SIGN = 0x80000000


def rows_per_step(n_blocks: int) -> int:
    """Block rows a wire-stream kernel walks per grid step: TILE_ROWS
    times the largest power of two <= MAX_TILES_PER_STEP that divides
    ``n_blocks / TILE_ROWS`` (n_blocks is a multiple of TILE_ROWS)."""
    g = MAX_TILES_PER_STEP
    while (n_blocks // TILE_ROWS) % g:
        g //= 2
    return g * TILE_ROWS


def win_lines(rows: int) -> int:
    """Lines of a step's window: its rows emit at most ``rows * BLOCK``
    words (2 lines a row at width 32), which behind the carried line's
    lane offset span ``2 * rows + 1`` lines; rounded to whole (8, 128)
    vregs."""
    return -(-(2 * rows + 1) // 8) * 8


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


def _row_spec(width, rows=TILE_ROWS):
    return pl.BlockSpec((rows, width), lambda i: (i, 0))


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


def _row_offsets(words_col, pos_vmem, pos_smem, sem):
    """Move a step's row offsets to SMEM: lane ``r`` of ``pos_smem`` row 0
    is block row ``r``'s word offset in the step (an exclusive prefix sum
    of the (rows, 1) words column), row 1 the inclusive one.  One prefix
    sum and one small DMA per step; no per-row vector-to-scalar moves."""
    shape = (words_col.shape[0], LANES)
    diag = (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            == jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    words = jnp.sum(jnp.where(diag, words_col, 0), axis=0, keepdims=True)
    incl = _row_cumsum(words)
    pos_vmem[0:1, :] = incl - words
    pos_vmem[1:2, :] = incl
    copy = pltpu.make_async_copy(pos_vmem, pos_smem, sem)
    copy.start()
    copy.wait()


def _window_start(start, hbm_ref, lines):
    """Clamp a step's word offset into the stream: a step past the
    provisioned capacity reads/writes the window-sized dump tail, never a
    valid word."""
    cap_lines = hbm_ref.shape[0] - lines
    s = jnp.minimum(start, cap_lines * LANES)
    return s // LANES, s % LANES


def _pack_groups(rows_ref, widths):
    """Pack the step's codes in ``rows_ref`` in place, one TILE_ROWS group
    at a time, so that each group's gather rounds run only to its own
    narrowest width; ``widths(s)`` gives the sub-width columns of the
    group's row slice ``s``."""
    def group(g, carry):
        s = pl.ds(pl.multiple_of(g * TILE_ROWS, TILE_ROWS), TILE_ROWS)
        rows_ref[s, :] = _pack_rows(rows_ref[s, :], widths(s))
        return carry

    jax.lax.fori_loop(0, rows_ref.shape[0] // TILE_ROWS, group, 0)


def _tile_loop(body, carry, unroll):
    """``carry = body(k, carry)`` for the TILE_ROWS rows of a tile.
    Unrolled for Mosaic, which then overlaps the rows' independent loads
    and rolls (a rolled loop ran the pack 1.4x and the unpack 2.5x slower
    on a v5e); a loop in interpret mode, where each unrolled copy would be
    compiled again."""
    if unroll:
        for k in range(TILE_ROWS):
            carry = body(k, carry)
        return carry
    return jax.lax.fori_loop(0, TILE_ROWS, body, carry)


def _emit_step(unroll, codes, widths, words_col, hbm_ref, off_ref, carry_ref,
               seg_ref, rows_ref, pos_vmem, pos_smem, sem, pos_sem):
    """Pack one step's block rows and append them to the HBM wire stream.

    Rows are placed back to back at the carried word offset inside a
    window that starts on the offset's line: each row's two lines are
    rotated by its lane offset and ORed into the (up to) three lines it
    covers, a tile of rows at a time.  Line 0 starts as the previous
    step's partial last line (blocks are word-aligned, so OR ==
    concatenation).  The window DMA waits for the previous one: they
    overlap on that line, the previous window's zero tail covers lines
    this one writes, and the window buffer is reused.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        off_ref[0] = 0
        carry_ref[...] = jnp.zeros_like(carry_ref)

    rows_ref[...] = codes
    _pack_groups(rows_ref, widths)
    _row_offsets(words_col, pos_vmem, pos_smem, pos_sem)
    rows, lines = rows_ref.shape[0], seg_ref.shape[0]
    start = off_ref[0]
    total = pos_smem[1, rows - 1]
    line0, b0 = _window_start(start, hbm_ref, lines)
    copy = pltpu.make_async_copy(
        seg_ref, hbm_ref.at[pl.ds(line0, lines)], sem)

    @pl.when(i > 0)
    def _():
        copy.wait()  # the previous step's window (same shape and semaphore)

    seg_ref[...] = jnp.zeros_like(seg_ref)
    seg_ref[0:1, :] = carry_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    near = jax.lax.broadcasted_iota(jnp.int32, (_GROUP_LINES, LANES), 0)

    def group(g, carry):  # one aligned window access per tile of rows
        r0 = pl.multiple_of(g * TILE_ROWS, TILE_ROWS)
        base = (b0 + pos_smem[0, r0]) // LANES // TILE_ROWS * TILE_ROWS

        def place(k, acc):
            p = b0 + pos_smem[0, r0 + k]
            line, c = p // LANES - base, p % LANES
            row = rows_ref[pl.ds(r0 + k, 1), :]
            lo = pltpu.roll(row[:, :LANES], c, 1)
            hi = pltpu.roll(row[:, LANES:], c, 1)
            wrapped = lane < c
            parts = (jnp.where(wrapped, jnp.uint32(0), lo),
                     jnp.where(wrapped, lo, hi),
                     jnp.where(wrapped, hi, jnp.uint32(0)))
            for j, part in enumerate(parts):
                acc = acc | jnp.where(near == line + j, part, jnp.uint32(0))
            return acc

        acc = _tile_loop(place, jnp.zeros((_GROUP_LINES, LANES), jnp.uint32),
                         unroll)
        at = pl.ds(pl.multiple_of(base, TILE_ROWS), _GROUP_LINES)
        seg_ref[at, :] = seg_ref[at, :] | acc
        return carry

    jax.lax.fori_loop(0, rows // TILE_ROWS, group, 0)
    carry_ref[...] = seg_ref[pl.ds((b0 + total) // LANES, 1), :]
    copy.start()

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        copy.wait()

    off_ref[0] = start + total


def _fetch_step(unroll, words_col, hbm_ref, off_ref, win_ref, rows_ref,
                pos_vmem, pos_smem, sem, pos_sem):
    """Read one step's block rows from the HBM wire stream, each moved to
    lane 0 of its row: -> (rows, BLOCK) words for ``_unpack_rows``.  The
    window DMA runs while the row offsets are computed."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        off_ref[0] = 0

    rows, lines = rows_ref.shape[0], win_ref.shape[0]
    start = off_ref[0]
    line0, b0 = _window_start(start, hbm_ref, lines)
    copy = pltpu.make_async_copy(
        hbm_ref.at[pl.ds(line0, lines)], win_ref, sem)
    copy.start()
    _row_offsets(words_col, pos_vmem, pos_smem, pos_sem)
    total = pos_smem[1, rows - 1]
    copy.wait()
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (TILE_ROWS, LANES), 0)

    def take(r):  # row r's (up to) three lines rotated back to lane 0
        p = b0 + pos_smem[0, r]
        line, c = p // LANES, p % LANES
        l0, l1, l2 = (pltpu.roll(win_ref[pl.ds(line + k, 1), :],
                                 (LANES - c) % LANES, 1) for k in range(3))
        first = lane < LANES - c
        return jnp.where(first, l0, l1), jnp.where(first, l1, l2)

    def group(g, carry):  # Mosaic stores rows only as whole aligned tiles
        r0 = pl.multiple_of(g * TILE_ROWS, TILE_ROWS)

        def gather(k, tile):
            row_lo, row_hi = take(r0 + k)
            return (jnp.where(sub == k, row_lo, tile[0]),
                    jnp.where(sub == k, row_hi, tile[1]))

        zeros = jnp.zeros((TILE_ROWS, LANES), jnp.uint32)
        lo, hi = _tile_loop(gather, (zeros, zeros), unroll)
        rows_ref[pl.ds(r0, TILE_ROWS), :] = jnp.concatenate([lo, hi], axis=1)
        return carry

    jax.lax.fori_loop(0, rows // TILE_ROWS, group, 0)
    off_ref[0] = start + total
    return rows_ref[...]


def _pack_scratch(rows):
    """The trailing arguments of ``_emit_step``: word-offset carry,
    partial-line carry, window, step rows, row offsets in VMEM and SMEM,
    and the window's and the offsets' DMA semaphores."""
    return [
        pltpu.SMEM((1,), jnp.int32),
        pltpu.VMEM((1, LANES), jnp.uint32),
        pltpu.VMEM((win_lines(rows), LANES), jnp.uint32),
        pltpu.VMEM((rows, BLOCK), jnp.uint32),
        pltpu.VMEM((2, LANES), jnp.int32),
        pltpu.SMEM((2, LANES), jnp.int32),
        pltpu.SemaphoreType.DMA(()),
        pltpu.SemaphoreType.DMA(()),
    ]


def _unpack_scratch(rows):
    """The trailing arguments of ``_fetch_step``: word-offset carry,
    window, step rows, row offsets in VMEM and SMEM, and the two DMA
    semaphores."""
    return [
        pltpu.SMEM((1,), jnp.int32),
        pltpu.VMEM((win_lines(rows), LANES), jnp.uint32),
        pltpu.VMEM((rows, BLOCK), jnp.uint32),
        pltpu.VMEM((2, LANES), jnp.int32),
        pltpu.SMEM((2, LANES), jnp.int32),
        pltpu.SemaphoreType.DMA(()),
        pltpu.SemaphoreType.DMA(()),
    ]


def stream_lines(capacity_words: int, rows: int) -> int:
    """Lines of the (lines, LANES) HBM view of a stream of this capacity,
    including the dump tail of one window of ``rows`` block rows."""
    return -(-capacity_words // LANES) + win_lines(rows)


def to_lines(packed: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Flat wire stream -> zero-padded (lines, LANES) view for the kernels."""
    n = packed.shape[0]
    return jnp.pad(packed, (0, stream_lines(n, rows) * LANES - n)).reshape(
        -1, LANES)


def _quantize_pack_kernel(unroll, x_ref, recip_ref, _zeros, packed_ref,
                          bw_ref, anchor_ref, *scratch):
    """quantize + zigzag + bitpack in one pass over the step."""
    zig, bw, anchor = _quantize_tile(x_ref[...], recip_ref[0, 0])
    bw_ref[...] = bw
    anchor_ref[...] = anchor
    _emit_step(unroll, zig, lambda s: [bw_ref[s, :]], _block_words([bw]),
               packed_ref, *scratch)


def _unpack_dequantize_reduce_kernel(unroll, packed_ref, bw_ref, anchor_ref,
                                     twoeb_ref, acc_ref, out_ref, *scratch):
    """Inverse fusion: packed words + acc -> acc + dequantize(unpack(words)).

    Same SMEM word-offset carry as the pack kernel; the step DMAs its
    word window from the HBM stream, so the uint32 codes array never
    materializes in HBM on the receive side either.
    """
    bw = bw_ref[...]
    w = _fetch_step(unroll, _block_words([bw]), packed_ref, *scratch)
    out_ref[...] = acc_ref[...] + _reconstruct(
        _unpack_rows(w, [bw]), anchor_ref[...], twoeb_ref[0, 0])


def _unpack_dequantize_kernel(unroll, packed_ref, bw_ref, anchor_ref,
                              twoeb_ref, out_ref, *scratch):
    """Pure fused decompress (no accumulator): the allgather/scatter receive
    path, which would otherwise pay a zero-accumulator materialization."""
    bw = bw_ref[...]
    w = _fetch_step(unroll, _block_words([bw]), packed_ref, *scratch)
    out_ref[...] = _reconstruct(_unpack_rows(w, [bw]), anchor_ref[...],
                                twoeb_ref[0, 0])


_N_PACK_SCRATCH = len(_pack_scratch(TILE_ROWS))


def _unpack_reduce_repack_kernel(emit_f32, unroll, packed_in_ref, bw_in_ref,
                                 anchor_in_ref, twoeb_ref, acc_ref, recip_ref,
                                 _zeros, *refs):
    """The single-pass ring hop (DESIGN.md §3.1): per step, fetch the
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
    w = _fetch_step(unroll, _block_words([bw_in]), packed_in_ref,
                    *scratch[_N_PACK_SCRATCH:])
    x = acc_ref[...] + _reconstruct(_unpack_rows(w, [bw_in]),
                                    anchor_in_ref[...], twoeb_ref[0, 0])
    zig, bw, anchor = _quantize_tile(x, recip_ref[0, 0])
    bw_out_ref[...] = bw
    anchor_out_ref[...] = anchor
    if emit_f32:
        outs[3][...] = x
    _emit_step(unroll, zig, lambda s: [bw_out_ref[s, :]], _block_words([bw]),
               packed_out_ref, *scratch[:_N_PACK_SCRATCH])


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
    rows = rows_per_step(n_blocks)
    twoeb = (2.0 * eb_in).reshape(1, 1).astype(jnp.float32)
    recip = (1.0 / (2.0 * eb_out)).reshape(1, 1).astype(jnp.float32)
    out_lines = stream_lines(capacity_words, rows)
    col = _row_spec(1, rows)
    out_specs = [_ANY, col, col]
    out_shape = [
        jax.ShapeDtypeStruct((out_lines, LANES), jnp.uint32),
        jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
        jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
    ]
    if emit_f32:
        out_specs.append(_row_spec(BLOCK, rows))
        out_shape.append(jax.ShapeDtypeStruct((n_blocks, BLOCK), jnp.float32))
    res = pl.pallas_call(
        functools.partial(_unpack_reduce_repack_kernel, emit_f32,
                          not interpret),
        grid=(n_blocks // rows,),
        in_specs=[
            _ANY,
            col,
            col,
            _scalar_spec(),
            _row_spec(BLOCK, rows),
            _scalar_spec(),
            _ANY,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=_pack_scratch(rows) + _unpack_scratch(rows),
        input_output_aliases={6: 0},
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(to_lines(packed, rows), bitwidth[:, None], anchor[:, None], twoeb, acc,
      recip, jnp.zeros((out_lines, LANES), jnp.uint32))
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
    rows = rows_per_step(n_blocks)
    recip = (1.0 / (2.0 * eb)).reshape(1, 1).astype(jnp.float32)
    lines = stream_lines(capacity_words, rows)
    col = _row_spec(1, rows)
    packed, bw, anchor = pl.pallas_call(
        functools.partial(_quantize_pack_kernel, not interpret),
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
    rows = rows_per_step(n_blocks)
    twoeb = (2.0 * eb).reshape(1, 1).astype(jnp.float32)
    col = _row_spec(1, rows)
    return pl.pallas_call(
        functools.partial(_unpack_dequantize_kernel, not interpret),
        grid=(n_blocks // rows,),
        in_specs=[_ANY, col, col, _scalar_spec()],
        out_specs=_row_spec(BLOCK, rows),
        out_shape=jax.ShapeDtypeStruct((n_blocks, BLOCK), jnp.float32),
        scratch_shapes=_unpack_scratch(rows),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(to_lines(packed, rows), bitwidth[:, None], anchor[:, None], twoeb)


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
    rows = rows_per_step(n_blocks)
    twoeb = (2.0 * eb).reshape(1, 1).astype(jnp.float32)
    col = _row_spec(1, rows)
    return pl.pallas_call(
        functools.partial(_unpack_dequantize_reduce_kernel, not interpret),
        grid=(n_blocks // rows,),
        in_specs=[_ANY, col, col, _scalar_spec(), _row_spec(BLOCK, rows)],
        out_specs=_row_spec(BLOCK, rows),
        out_shape=jax.ShapeDtypeStruct((n_blocks, BLOCK), jnp.float32),
        scratch_shapes=_unpack_scratch(rows),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(to_lines(packed, rows), bitwidth[:, None], anchor[:, None], twoeb, acc)


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
