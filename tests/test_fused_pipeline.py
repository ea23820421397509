"""Fused quantize->pack pipeline + chunked pipelined ring collectives.

Three contracts (ISSUE 1 acceptance criteria):

  1. ``quantize_pack`` produces a BYTE-IDENTICAL packed stream to the
     unfused ``quantize`` + ``bitpack.pack`` composition (oracle test),
     including when the stream overflows the provisioned capacity.
  2. ``unpack_dequantize_reduce`` matches its unfused oracle and the
     fused/unfused compressors interoperate on the same wire format.
  3. The pipelined (chunked double-buffered) ring schedules return the
     same results as the sequential ones — bitwise when piece boundaries
     align with the sequential chunking, within the documented error
     budget otherwise — and ``intring`` stays bitwise rank-identical.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import bitpack
from repro.core.compressed import capacity_words_for
from repro.core.compressor import ErrorBoundedLorenzo
from repro.kernels import lorenzo, ops, ref

EB = 1e-3
# Block rows giving every walk of the stream kernels: rows_per_step 8
# (three steps), 32 (one step), 32 over a tile count that is not a power
# of two (96 rows, three steps) and 128 (two steps).
STEP_ROWS = [24, 32, 96, 256]


def _field(rng, n):
    smooth = np.cumsum(rng.normal(0, 0.02, n))
    rough = rng.normal(0, 1.0, n) * (rng.random(n) < 0.05)
    out = (smooth + rough).astype(np.float32)
    out[:: max(n // 13, 1)] = 0.0
    return out


def _plant_widths(x, eb, rng):
    """Give every TILE_ROWS tile a row at each extreme width: row 1 at 32
    bits (quanta up to 2**30, so deltas reach 2**31), row 2 at 1 bit
    (deltas of -1), row 5 at 0 bits."""
    x = x.copy()
    t = lorenzo.TILE_ROWS
    x[1::t] = rng.uniform(-1, 1, x[1::t].shape) * 2.0 ** 30 * 2 * eb
    x[2::t] = -np.arange(lorenzo.BLOCK) * 2 * eb
    x[5::t] = 0.0
    return x


# ---------------------------------------------------------------------------
# 1. Fused pack vs oracle — byte identity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("eb", [1e-2, 1e-3, 1e-4])
@pytest.mark.parametrize("rows", [8, 16, 64, 96, 256])
def test_quantize_pack_byte_identical_to_unfused(eb, rows):
    rng = np.random.default_rng(rows)
    x = _field(rng, rows * lorenzo.BLOCK).reshape(rows, lorenzo.BLOCK)
    x = _plant_widths(x, eb, rng)
    cap = capacity_words_for(x.size, 1.2, lorenzo.BLOCK)
    pk_f, bw_f, an_f = ops.quantize_pack(jnp.asarray(x), eb, cap)
    pk_r, bw_r, an_r = ref.quantize_pack_ref(jnp.asarray(x), jnp.float32(eb), cap)
    np.testing.assert_array_equal(np.asarray(bw_f), np.asarray(bw_r))
    np.testing.assert_array_equal(np.asarray(an_f), np.asarray(an_r))
    np.testing.assert_array_equal(np.asarray(pk_f), np.asarray(pk_r))


@pytest.mark.parametrize("rows", STEP_ROWS)
@pytest.mark.parametrize("cap", [64, 2000])
def test_quantize_pack_byte_identical_under_overflow(rows, cap):
    """Capacity overflow: valid words stay byte-identical, the overflowing
    tail is dropped in both paths, and nwords flags the condition.  A
    capacity of 2000 words runs out inside a later step of 128 rows."""
    rng = np.random.default_rng(7)
    x = rng.normal(0, 100.0, (rows, lorenzo.BLOCK)).astype(np.float32)  # rough
    x = _plant_widths(x, EB, rng)
    pk_f, bw_f, an_f = ops.quantize_pack(jnp.asarray(x), EB, cap)
    pk_r, bw_r, _ = ref.quantize_pack_ref(jnp.asarray(x), jnp.float32(EB), cap)
    np.testing.assert_array_equal(np.asarray(pk_f), np.asarray(pk_r))
    np.testing.assert_array_equal(np.asarray(bw_f), np.asarray(bw_r))
    nwords = int(bitpack.packed_words(jnp.asarray(bw_f), lorenzo.BLOCK))
    assert nwords > cap  # genuinely overflowed
    assert pk_f.shape == (cap,)  # never silently grows
    # the receive side decodes every block whose words fit the capacity
    fits = np.cumsum(np.asarray(bw_r) * lorenzo.BLOCK // 32) <= cap
    got = ops.unpack_dequantize(pk_f, bw_f, an_f, EB)
    want = ref.dequantize_ref(bitpack.unpack(pk_r, bw_r, lorenzo.BLOCK),
                              an_f, jnp.float32(EB))
    np.testing.assert_array_equal(np.asarray(got)[fits],
                                  np.asarray(want)[fits])


@pytest.mark.parametrize("rows", [8] + STEP_ROWS)
def test_fused_pack_round_trip_at_extreme_widths(rows):
    """Every tile mixing bitwidths 0, 1, 2 and 32: the pack kernel's gather
    rounds run to the narrowest width (33 rounds at b=1), and the wire
    window carries rows from empty to full 256 words."""
    rng = np.random.default_rng(2)
    x = np.zeros((rows, lorenzo.BLOCK), np.float32)
    t = lorenzo.TILE_ROWS
    x[1::t] = rng.normal(0, 1e6, x[1::t].shape)  # 32 bits
    x[2::t] = -np.arange(lorenzo.BLOCK) * 2 * EB  # deltas of -1: 1 bit
    x[3::t, 7] = 2 * EB  # one spike: 2 bits
    x[5::t] = rng.normal(0, 1e6, x[5::t].shape)
    cap = capacity_words_for(x.size, 1.2, lorenzo.BLOCK)
    pk, bw, an = ops.quantize_pack(jnp.asarray(x), EB, cap)
    pk_r, bw_r, an_r = ref.quantize_pack_ref(jnp.asarray(x), jnp.float32(EB), cap)
    assert sorted(set(np.asarray(bw_r).tolist())) == [0, 1, 2, 32]
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pk_r))
    got = ops.unpack_dequantize(pk, bw, an, EB)
    want = ref.dequantize_ref(bitpack.unpack(pk_r, bw_r, lorenzo.BLOCK), an_r,
                              jnp.float32(EB))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("eb", [1e-2, 1e-4])
@pytest.mark.parametrize("rows", STEP_ROWS)
def test_unpack_dequantize_reduce_matches_oracle(eb, rows):
    rng = np.random.default_rng(3)
    x = _field(rng, rows * lorenzo.BLOCK).reshape(rows, lorenzo.BLOCK)
    x = _plant_widths(x, eb, rng)
    acc = rng.normal(0, 1, x.shape).astype(np.float32)
    # no addend on the planted 32-bit rows, whose sums would differ by
    # the one-ulp fused multiply-add slack at values near 2**31 eb
    acc[1::lorenzo.TILE_ROWS] = 0.0
    cap = capacity_words_for(x.size, 1.2, lorenzo.BLOCK)
    pk, bw, an = ops.quantize_pack(jnp.asarray(x), eb, cap)
    got = ops.unpack_dequantize_reduce(pk, bw, an, eb, jnp.asarray(acc))
    want = ref.unpack_dequantize_reduce_ref(
        pk, bw, an, jnp.float32(eb), jnp.asarray(acc)
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-6)
    # end-to-end compressor invariant through the fused pipeline, on the
    # rows of the field (the planted 32-bit rows hold values up to 2**31 eb)
    field = np.arange(rows) % lorenzo.TILE_ROWS != 1
    err = np.abs(np.asarray(got) - acc - x)[field].max()
    assert err <= eb * (1 + 1e-3) + np.abs(x[field]).max() * 2e-7


@pytest.mark.parametrize("eb", [1e-2, 1e-4])
@pytest.mark.parametrize("rows", [16] + STEP_ROWS)
def test_unpack_dequantize_no_acc_matches_dequantize(eb, rows):
    """The accumulator-free fused decompress equals unpack+dequantize
    exactly (it is the allgather/scatter receive path)."""
    rng = np.random.default_rng(11)
    x = _field(rng, rows * lorenzo.BLOCK).reshape(rows, lorenzo.BLOCK)
    x = _plant_widths(x, eb, rng)
    cap = capacity_words_for(x.size, 1.2, lorenzo.BLOCK)
    pk, bw, an = ops.quantize_pack(jnp.asarray(x), eb, cap)
    got = ops.unpack_dequantize(pk, bw, an, eb)
    codes = bitpack.unpack(pk, bw, lorenzo.BLOCK)
    want = ref.dequantize_ref(codes, an, jnp.float32(eb))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("lossless", [False, True])
@pytest.mark.parametrize("rows", STEP_ROWS)
@pytest.mark.parametrize("starved", [False, True])
def test_entropy_kernels_match_oracle(rows, lossless, starved):
    """The three entropy kernels walk the stream like the dense ones: the
    packed words, descriptors and anchors equal the jnp oracle's
    (``core.entropy``), also when a starved capacity overflows (then
    flagged by the descriptors' word count), and every block whose words
    fit decodes exactly."""
    from repro.core import entropy

    rng = np.random.default_rng(rows + 2 * lossless)
    x = _field(rng, rows * lorenzo.BLOCK).reshape(rows, lorenzo.BLOCK)
    x = jnp.asarray(_plant_widths(x, EB, rng))
    cap = 2000 if starved else rows * lorenzo.BLOCK
    codes, anchor = entropy.encode_blocks(x, EB, lossless=lossless)
    pk_r, desc_r, nwords = entropy.pack(codes, cap)
    pk, desc, an = ops.entropy_quantize_pack(x, EB, cap, lossless=lossless)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pk_r))
    np.testing.assert_array_equal(np.asarray(desc), np.asarray(desc_r))
    np.testing.assert_array_equal(np.asarray(an), np.asarray(anchor))
    assert (int(nwords) > cap) == starved
    words = np.asarray(entropy.split_desc(desc_r)).sum(axis=1) * (
        entropy.SUB_WORDS_PER_BIT)
    fits = np.cumsum(words) <= cap
    got = ops.entropy_unpack_dequantize(pk, desc, an, EB, lossless=lossless)
    want = entropy.decode_blocks(entropy.unpack(pk_r, desc_r, lorenzo.BLOCK),
                                 anchor, EB, lossless=lossless)
    np.testing.assert_array_equal(np.asarray(got)[fits].view(np.uint32),
                                  np.asarray(want)[fits].view(np.uint32))
    acc = rng.normal(0, 1, x.shape).astype(np.float32)
    acc[1::lorenzo.TILE_ROWS] = 0.0  # as in the dense reduce test
    red = ops.entropy_unpack_dequantize_reduce(pk, desc, an, EB,
                                               jnp.asarray(acc),
                                               lossless=lossless)
    np.testing.assert_allclose(np.asarray(red)[fits],
                               (acc + np.asarray(got))[fits], rtol=0, atol=1e-6)


@pytest.mark.parametrize("n", [1, 255, 4097, 50_000])
def test_fused_and_unfused_compressors_interoperate(n):
    """Same wire container either way: fused-compressed payloads decompress
    identically through the unfused path and vice versa."""
    rng = np.random.default_rng(n)
    x = jnp.asarray(np.cumsum(rng.normal(0, 0.01, n)).astype(np.float32))
    fused = ErrorBoundedLorenzo(capacity_factor=1.2, fused=True)
    unfused = ErrorBoundedLorenzo(capacity_factor=1.2, fused=False)
    c_f, c_u = fused.compress(x, EB), unfused.compress(x, EB)
    np.testing.assert_array_equal(np.asarray(c_f.packed), np.asarray(c_u.packed))
    assert int(c_f.nwords) == int(c_u.nwords)
    np.testing.assert_array_equal(
        np.asarray(unfused.decompress(c_f)), np.asarray(fused.decompress(c_u))
    )
    acc = jnp.asarray(rng.normal(0, 1, n).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(fused.decompress_reduce(c_u, acc)),
        np.asarray(unfused.decompress_reduce(c_f, acc)),
        rtol=0, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# 2. Pipelined vs sequential ring schedules (single-device piece simulator)
# ---------------------------------------------------------------------------


def _sim_rs_ring(xs, eb_stage, piece_splits, comp):
    """Global-view ring reduce-scatter with each chunk in `piece_splits`
    pieces — the schedule of _reduce_scatter_ring_pipelined (owner_offset=0,
    piece order within a step preserved)."""
    n = len(xs)
    d = xs[0].shape[0]
    assert d % (n * piece_splits) == 0
    chunk = d // n
    piece = chunk // piece_splits

    def rt(v):
        c = comp.compress(jnp.asarray(v), eb_stage)
        return np.asarray(comp.decompress(c))

    acc = [x.astype(np.float32).copy() for x in xs]
    for s in range(n - 1):
        for p in range(piece_splits):
            sends = [
                rt(acc[r][((r - s) % n) * chunk + p * piece:][:piece])
                for r in range(n)
            ]
            for r in range(n):
                lo = ((r - s - 1) % n) * chunk + p * piece
                acc[r][lo : lo + piece] += sends[(r - 1) % n]
    return acc, chunk, piece


@pytest.mark.parametrize("n", [4, 8])
def test_pipelined_rs_bitwise_equals_sequential_when_aligned(n):
    """Piece boundaries are whole compressor tiles, so the quantization grid
    — and hence every intermediate value — matches the sequential schedule
    exactly when the sequential chunking is piece-aligned."""
    P = 2
    quantum = lorenzo.BLOCK * lorenzo.TILE_ROWS
    d = n * P * quantum
    rng = np.random.default_rng(n)
    xs = [np.cumsum(rng.normal(0, 0.01, d)).astype(np.float32) for _ in range(n)]
    comp = ErrorBoundedLorenzo(capacity_factor=1.2)
    eb_stage = EB / n
    seq, _, _ = _sim_rs_ring(xs, eb_stage, 1, comp)
    pip, _, _ = _sim_rs_ring(xs, eb_stage, P, comp)
    for a, b in zip(seq, pip):
        np.testing.assert_array_equal(a, b)


def test_pipelined_rs_within_budget_when_unaligned():
    n, P = 4, 4
    quantum = lorenzo.BLOCK * lorenzo.TILE_ROWS
    d = n * P * quantum
    rng = np.random.default_rng(0)
    xs = [np.cumsum(rng.normal(0, 0.01, d)).astype(np.float32) for _ in range(n)]
    comp = ErrorBoundedLorenzo(capacity_factor=1.2)
    eb_stage = EB / n
    pip, chunk, _ = _sim_rs_ring(xs, eb_stage, P, comp)
    exact = np.sum(xs, axis=0)
    for r in range(n):
        lo = ((r + 1) % n) * chunk
        got = pip[r][lo : lo + chunk]
        err = np.abs(got - exact[lo : lo + chunk]).max()
        assert err <= (n - 1) * eb_stage + np.abs(exact).max() * 1e-6


# ---------------------------------------------------------------------------
# 3. Cost model + selector acceptance (pipelined dominates above saturation)
# ---------------------------------------------------------------------------


def test_pipelined_ring_dominates_above_saturation_and_selected():
    from repro.core import cost_model as cm
    from repro.core.selector import select_allreduce_plan

    for hw in (cm.A100_SLINGSHOT, cm.TPU_V5E):
        D, N, R = 646e6, 8, 20
        assert D / N / 1e6 > hw.cmp_saturation_mb  # chunks stay saturated
        best = cm.best_pipeline_chunks(D, N, R, hw)
        assert best > 1
        assert cm.allreduce_ring_gz_chunked(D, N, R, hw, best) < \
            cm.allreduce_ring_gz_chunked(D, N, R, hw, 1)
        algo, chunks = select_allreduce_plan(int(D), N, R, hw)
        assert (algo, chunks) == ("ring", best)


def test_chunked_model_degrades_to_sequential_below_saturation():
    from repro.core import cost_model as cm

    for hw in (cm.A100_SLINGSHOT, cm.TPU_V5E):
        D, N = 1e6, 64  # 16 KB chunks: overhead-dominated
        assert cm.best_pipeline_chunks(D, N, 20, hw) == 1
