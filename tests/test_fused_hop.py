"""Single-pass ring hop (ISSUE 2): unpack→reduce→repack in one kernel.

Contracts:

  1. The fused ``unpack_reduce_repack`` kernel is BYTE-IDENTICAL to the
     PR 1 two-kernel composition (``unpack_dequantize_reduce`` then
     ``quantize_pack``) — wire words, bitwidths, anchors, and the f32
     intermediate — including under capacity overflow of the output.
  2. ``ErrorBoundedLorenzo.decompress_reduce_compress`` fused vs the
     decompress_reduce ∘ compress composition: byte-identical Compressed
     payloads across shapes, error bounds and piece alignments (hypothesis
     property test + deterministic sweep), and the overflow flag agrees.
  3. The fused-hop cost model: one ``cmp_overhead_us`` per piece-hop
     instead of two ⇒ ``best_pipeline_chunks`` selects STRICTLY deeper
     pipelines at calibrated (D, N) points, and the selector's ring plan
     picks it up.  (Planner defaults are fused_hop=True, matching
     GZConfig — the two-kernel model is requested explicitly.)

(The 8-device bitwise-equality of the fused-hop ring/redoub schedules vs
the PR 1 two-kernel path lives in tests/_mp_collectives_child.py.)
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.compressed import capacity_words_for
from repro.core.compressor import ErrorBoundedLorenzo
from repro.kernels import lorenzo, ops

B = lorenzo.BLOCK
QUANTUM = lorenzo.BLOCK * lorenzo.TILE_ROWS


def _field(rng, n, kind):
    if kind == "smooth":
        return np.cumsum(rng.normal(0, 0.02, n)).astype(np.float32)
    if kind == "boundary":  # values near quantization half-grid points
        k = rng.integers(-1000, 1000, n)
        return ((k + 0.5) * 2e-3 + rng.normal(0, 1e-9, n)).astype(np.float32)
    return (rng.normal(0, 1.0, n) * (rng.random(n) < 0.2)).astype(np.float32)


# ---------------------------------------------------------------------------
# 1. Kernel-level byte identity vs the two-kernel composition
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["smooth", "boundary", "spiky"])
@pytest.mark.parametrize("eb_in,eb_out", [(1e-3, 1e-3), (1e-2, 1e-4)])
@pytest.mark.parametrize("rows", [24, 96, 256])  # 8, 32, 128 rows a step
def test_fused_hop_kernel_byte_identical_to_composition(kind, eb_in, eb_out,
                                                        rows):
    # deterministic per-parametrization seed (hash() is salted per process)
    seed = ["smooth", "boundary", "spiky"].index(kind) * 10 + int(eb_in * 1e4)
    rng = np.random.default_rng(seed)
    x2 = jnp.asarray(_field(rng, rows * B, kind).reshape(rows, B))
    a2 = jnp.asarray(rng.normal(0, 1, (rows, B)).astype(np.float32))
    cap = capacity_words_for(rows * B, 1.3, B)
    pk, bw, an = ops.quantize_pack(x2, eb_in, cap)
    fp, fb, fa, fx = ops.unpack_reduce_repack(
        pk, bw, an, eb_in, a2, eb_out, cap, emit_f32=True
    )
    ux = ops.unpack_dequantize_reduce(pk, bw, an, eb_in, a2)
    cp, cb, ca = ops.quantize_pack(ux, eb_out, cap)
    np.testing.assert_array_equal(np.asarray(fx), np.asarray(ux))
    np.testing.assert_array_equal(np.asarray(fp), np.asarray(cp))
    np.testing.assert_array_equal(np.asarray(fb), np.asarray(cb))
    np.testing.assert_array_equal(np.asarray(fa), np.asarray(ca))
    # no-f32 variant emits the same stream
    gp, gb, ga = ops.unpack_reduce_repack(pk, bw, an, eb_in, a2, eb_out, cap)
    np.testing.assert_array_equal(np.asarray(gp), np.asarray(cp))
    np.testing.assert_array_equal(np.asarray(gb), np.asarray(cb))
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(ca))


@pytest.mark.parametrize("rows,small", [(32, 64), (256, 64), (256, 5000)])
def test_fused_hop_kernel_byte_identical_under_output_overflow(rows, small):
    """A starved OUTPUT capacity truncates both paths identically: the
    valid prefix stays byte-identical, the overflow lands in the dump
    tail, and the stream never silently grows (5000 words run out inside
    the first step of 128 rows, so the second starts on the dump tail)."""
    rng = np.random.default_rng(5)
    x2 = jnp.asarray(rng.normal(0, 100.0, (rows, B)).astype(np.float32))
    a2 = jnp.asarray(rng.normal(0, 1, (rows, B)).astype(np.float32))
    cap_in = capacity_words_for(rows * B, 1.3, B)
    pk, bw, an = ops.quantize_pack(x2, 1e-3, cap_in)
    for emit in (False, True):
        fp, fb = ops.unpack_reduce_repack(pk, bw, an, 1e-3, a2, 1e-3, small,
                                          emit_f32=emit)[:2]
        ux = ops.unpack_dequantize_reduce(pk, bw, an, 1e-3, a2)
        cp, _, _ = ops.quantize_pack(ux, 1e-3, small)
        np.testing.assert_array_equal(np.asarray(fp), np.asarray(cp))
    assert fp.shape == (small,)
    from repro.core import bitpack

    assert int(bitpack.packed_words(fb, B)) > small  # genuinely overflowed


# ---------------------------------------------------------------------------
# 2. Compressor-level: decompress_reduce_compress fused == composition
# ---------------------------------------------------------------------------


def _assert_hop_identical(n, eb_in, eb_out, seed, kind="smooth"):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(_field(rng, n, kind))
    acc = jnp.asarray(rng.normal(0, 1, n).astype(np.float32))
    fused = ErrorBoundedLorenzo(capacity_factor=1.3, fused=True)
    unfused = ErrorBoundedLorenzo(capacity_factor=1.3, fused=False)
    c = fused.compress(x, eb_in)
    cf, uf = fused.decompress_reduce_compress(
        c, acc, eb_out, return_updated=True
    )
    cu, uu = unfused.decompress_reduce_compress(
        c, acc, eb_out, return_updated=True
    )
    np.testing.assert_array_equal(np.asarray(cf.packed), np.asarray(cu.packed))
    np.testing.assert_array_equal(np.asarray(cf.bitwidth), np.asarray(cu.bitwidth))
    np.testing.assert_array_equal(np.asarray(cf.anchor), np.asarray(cu.anchor))
    assert int(cf.nwords) == int(cu.nwords)
    np.testing.assert_array_equal(np.asarray(uf), np.asarray(uu))
    # the emitted stream is what compress(updated) would have produced
    c2 = fused.compress(uu, eb_out)
    np.testing.assert_array_equal(np.asarray(cf.packed), np.asarray(c2.packed))


@pytest.mark.parametrize("n", [1, 255, B, QUANTUM - 7, QUANTUM, 3 * QUANTUM + 513])
def test_decompress_reduce_compress_fused_equals_composition(n):
    """Byte identity across piece alignments: whole tiles, partial blocks,
    single elements — the padded-tail values reconstruct to exact 0.0 in
    both paths, so the quantization grid never diverges."""
    _assert_hop_identical(n, 1e-3, 1e-3, seed=n)
    _assert_hop_identical(n, 1e-2, 1e-4, seed=n + 1, kind="spiky")


def test_decompress_reduce_compress_overflow_flag_agrees():
    rng = np.random.default_rng(11)
    n = 2 * QUANTUM
    x = jnp.asarray(rng.normal(0, 100.0, n).astype(np.float32))
    acc = jnp.asarray(rng.normal(0, 1, n).astype(np.float32))
    for fused in (True, False):
        comp = ErrorBoundedLorenzo(capacity_factor=0.02, fused=fused)
        c = comp.compress(x, 1e-6)
        c_out, _ = comp.decompress_reduce_compress(c, acc)
        assert bool(c_out.overflowed()), f"fused={fused}"


# ---------------------------------------------------------------------------
# 3. Cost model: the fused hop buys strictly deeper pipelines
# ---------------------------------------------------------------------------


def test_fused_hop_cheaper_at_fixed_depth():
    from repro.core import cost_model as cm

    for hw in (cm.TPU_V5E, cm.A100_SLINGSHOT):
        for chunks in (1, 2, 4, 8):
            f = cm.allreduce_ring_gz_chunked(646e6, 8, 20, hw, chunks,
                                             fused_hop=True)
            u = cm.allreduce_ring_gz_chunked(646e6, 8, 20, hw, chunks,
                                             fused_hop=False)
            assert f < u, (hw.name, chunks)


def test_t_hop_fused_single_overhead():
    from repro.core import cost_model as cm

    for hw in (cm.TPU_V5E, cm.A100_SLINGSHOT):
        size = 1e6
        two_kernel = (cm.t_compress(size, hw) + cm.t_decompress(size, hw)
                      + cm.t_reduce(size, hw))
        fused = cm.t_hop_fused(size, hw)
        assert fused < two_kernel
        # exactly one per-invocation overhead in the fused hop
        work = fused - hw.cmp_overhead_us * 1e-6
        assert work > 0
        assert two_kernel - fused >= hw.cmp_overhead_us * 1e-6


def test_fused_hop_strictly_deeper_at_calibrated_points():
    """Acceptance: the halved per-piece overhead moves the overhead-vs-
    overlap break-even, so ``best_pipeline_chunks`` selects a STRICTLY
    deeper pipeline at calibrated (D, N) points on both hardware models —
    and at those points the deeper schedule is a real win under the fused
    model (not a tie broken differently)."""
    from repro.core import cost_model as cm

    strictly = {cm.TPU_V5E.name: 0, cm.A100_SLINGSHOT.name: 0}
    for hw in (cm.TPU_V5E, cm.A100_SLINGSHOT):
        for D in (64e6, 323e6, 646e6, 1.3e9):
            for N in (8, 16, 32, 64):
                for R in (3, 6, 20):
                    u = cm.best_pipeline_chunks(D, N, R, hw, fused_hop=False)
                    f = cm.best_pipeline_chunks(D, N, R, hw, fused_hop=True)
                    if f > u:
                        strictly[hw.name] += 1
                        assert cm.allreduce_ring_gz_chunked(
                            D, N, R, hw, f, fused_hop=True
                        ) < cm.allreduce_ring_gz_chunked(
                            D, N, R, hw, u, fused_hop=True
                        )
    assert all(v > 0 for v in strictly.values()), strictly


def test_selector_plan_picks_deeper_fused_ring():
    """At a calibrated point where the fused optimum is strictly deeper,
    the selector's ring plan follows the fused model."""
    from repro.core import cost_model as cm
    from repro.core.selector import select_allreduce_plan

    D, N, R, hw = 646e6, 16, 20, cm.A100_SLINGSHOT
    u = cm.best_pipeline_chunks(D, N, R, hw, fused_hop=False)
    f = cm.best_pipeline_chunks(D, N, R, hw, fused_hop=True)
    assert f > u
    algo_f, chunks_f = select_allreduce_plan(int(D), N, R, hw, fused_hop=True)
    if algo_f == "ring":
        assert chunks_f == f


def test_planner_respects_fused_hop_flag():
    from repro.core.collectives import plan_ring_pipeline_chunks

    # big payloads so the fill cap never binds
    n_elems = int(646e6 / 4)
    for n_ranks in (8, 16, 32):
        u = plan_ring_pipeline_chunks(n_elems, n_ranks, fused_hop=False)
        f = plan_ring_pipeline_chunks(n_elems, n_ranks, fused_hop=True)
        assert f >= u
