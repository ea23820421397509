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


def _field(rng, n):
    smooth = np.cumsum(rng.normal(0, 0.02, n))
    rough = rng.normal(0, 1.0, n) * (rng.random(n) < 0.05)
    out = (smooth + rough).astype(np.float32)
    out[:: max(n // 13, 1)] = 0.0
    return out


# ---------------------------------------------------------------------------
# 1. Fused pack vs oracle — byte identity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("eb", [1e-2, 1e-3, 1e-4])
@pytest.mark.parametrize("rows", [8, 16, 64])
def test_quantize_pack_byte_identical_to_unfused(eb, rows):
    rng = np.random.default_rng(rows)
    x = _field(rng, rows * lorenzo.BLOCK).reshape(rows, lorenzo.BLOCK)
    cap = capacity_words_for(x.size, 1.2, lorenzo.BLOCK)
    pk_f, bw_f, an_f = ops.quantize_pack(jnp.asarray(x), eb, cap)
    pk_r, bw_r, an_r = ref.quantize_pack_ref(jnp.asarray(x), jnp.float32(eb), cap)
    np.testing.assert_array_equal(np.asarray(bw_f), np.asarray(bw_r))
    np.testing.assert_array_equal(np.asarray(an_f), np.asarray(an_r))
    np.testing.assert_array_equal(np.asarray(pk_f), np.asarray(pk_r))


def test_quantize_pack_byte_identical_under_overflow():
    """Capacity overflow: valid words stay byte-identical, the overflowing
    tail is dropped in both paths, and nwords flags the condition."""
    rng = np.random.default_rng(7)
    rows = 32
    x = rng.normal(0, 100.0, (rows, lorenzo.BLOCK)).astype(np.float32)  # rough
    cap = 64  # far too small on purpose
    pk_f, bw_f, _ = ops.quantize_pack(jnp.asarray(x), EB, cap)
    pk_r, bw_r, _ = ref.quantize_pack_ref(jnp.asarray(x), jnp.float32(EB), cap)
    np.testing.assert_array_equal(np.asarray(pk_f), np.asarray(pk_r))
    nwords = int(bitpack.packed_words(jnp.asarray(bw_f), lorenzo.BLOCK))
    assert nwords > cap  # genuinely overflowed
    assert pk_f.shape == (cap,)  # never silently grows


def test_fused_pack_round_trip_at_extreme_widths():
    """One tile mixing bitwidths 0, 1, 2 and 32: the pack kernel's gather
    rounds run to the narrowest width (33 rounds at b=1), and the wire
    window carries rows from empty to full 256 words."""
    rng = np.random.default_rng(2)
    x = np.zeros((lorenzo.TILE_ROWS, lorenzo.BLOCK), np.float32)
    x[1] = rng.normal(0, 1e6, lorenzo.BLOCK)  # 32 bits
    x[2] = -np.arange(lorenzo.BLOCK) * 2 * EB  # deltas of -1: 1 bit
    x[3, 7] = 2 * EB  # one spike: 2 bits
    x[5] = rng.normal(0, 1e6, lorenzo.BLOCK)
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
def test_unpack_dequantize_reduce_matches_oracle(eb):
    rng = np.random.default_rng(3)
    rows = 24
    x = _field(rng, rows * lorenzo.BLOCK).reshape(rows, lorenzo.BLOCK)
    acc = rng.normal(0, 1, x.shape).astype(np.float32)
    cap = capacity_words_for(x.size, 1.2, lorenzo.BLOCK)
    pk, bw, an = ops.quantize_pack(jnp.asarray(x), eb, cap)
    got = ops.unpack_dequantize_reduce(pk, bw, an, eb, jnp.asarray(acc))
    want = ref.unpack_dequantize_reduce_ref(
        pk, bw, an, jnp.float32(eb), jnp.asarray(acc)
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-6)
    # end-to-end compressor invariant through the fused pipeline
    err = np.abs(np.asarray(got) - acc - x).max()
    assert err <= eb * (1 + 1e-3) + np.abs(x).max() * 2e-7


@pytest.mark.parametrize("eb", [1e-2, 1e-4])
def test_unpack_dequantize_no_acc_matches_dequantize(eb):
    """The accumulator-free fused decompress equals unpack+dequantize
    exactly (it is the allgather/scatter receive path)."""
    rng = np.random.default_rng(11)
    rows = 16
    x = _field(rng, rows * lorenzo.BLOCK).reshape(rows, lorenzo.BLOCK)
    cap = capacity_words_for(x.size, 1.2, lorenzo.BLOCK)
    pk, bw, an = ops.quantize_pack(jnp.asarray(x), eb, cap)
    got = ops.unpack_dequantize(pk, bw, an, eb)
    codes = bitpack.unpack(pk, bw, lorenzo.BLOCK)
    want = ref.dequantize_ref(codes, an, jnp.float32(eb))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


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
