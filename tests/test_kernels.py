"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import lorenzo, ops, ref

EBS = [1e-2, 1e-3, 1e-4]


def _field(rng, n):
    """Smooth 'scientific' field plus some rough noise and exact zeros."""
    smooth = np.cumsum(rng.normal(0, 0.02, n))
    rough = rng.normal(0, 1.0, n) * (rng.random(n) < 0.05)
    out = (smooth + rough).astype(np.float32)
    out[:: max(n // 13, 1)] = 0.0
    return out


@pytest.mark.parametrize("eb", EBS)
@pytest.mark.parametrize("rows", [8, 16, 64])
def test_quantize_matches_ref(eb, rows):
    rng = np.random.default_rng(rows)
    x = _field(rng, rows * lorenzo.BLOCK).reshape(rows, lorenzo.BLOCK)
    ck, bk, ak = ops.quantize(jnp.asarray(x), eb)
    cr, br, ar = ref.quantize_ref(jnp.asarray(x), jnp.float32(eb))
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(bk), np.asarray(br))
    np.testing.assert_array_equal(np.asarray(ak), np.asarray(ar))


@pytest.mark.parametrize("eb", EBS)
@pytest.mark.parametrize("rows", [8, 32])
def test_dequantize_matches_ref(eb, rows):
    rng = np.random.default_rng(rows + 1)
    x = _field(rng, rows * lorenzo.BLOCK).reshape(rows, lorenzo.BLOCK)
    codes, _, anchor = ref.quantize_ref(jnp.asarray(x), jnp.float32(eb))
    dk = ops.dequantize(codes, anchor, eb)
    dr = ref.dequantize_ref(codes, anchor, jnp.float32(eb))
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr), rtol=0, atol=0)


@pytest.mark.parametrize("eb", EBS)
def test_fused_dequantize_reduce_matches_ref(eb):
    rows = 16
    rng = np.random.default_rng(7)
    x = _field(rng, rows * lorenzo.BLOCK).reshape(rows, lorenzo.BLOCK)
    acc = rng.normal(0, 1, x.shape).astype(np.float32)
    codes, _, anchor = ref.quantize_ref(jnp.asarray(x), jnp.float32(eb))
    got = ops.dequantize_reduce(codes, anchor, eb, jnp.asarray(acc))
    want = ref.dequantize_reduce_ref(codes, anchor, jnp.float32(eb), jnp.asarray(acc))
    # fused multiply-add ordering differs from the two-op oracle: 1-ulp slack
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-6)


@pytest.mark.parametrize("eb", EBS)
@pytest.mark.parametrize("rows", [8, 24, 96, 256])
def test_error_bound_holds_end_to_end(eb, rows):
    """The fundamental compressor invariant: |x - x'| <= eb, and the wire
    stream kernels (8, 32 and 128 block rows a grid step at these sizes)
    give the same reconstruction as the unpacked ones."""
    rng = np.random.default_rng(rows)
    x = _field(rng, rows * lorenzo.BLOCK).reshape(rows, lorenzo.BLOCK)
    codes, _, anchor = ops.quantize(jnp.asarray(x), eb)
    x2 = np.asarray(ops.dequantize(codes, anchor, eb))
    # eb plus f32 relative rounding of q*2eb for large |x|
    assert np.abs(x - x2).max() <= eb * (1 + 1e-3) + np.abs(x).max() * 2e-7
    packed, bw, anchor_p = ops.quantize_pack(jnp.asarray(x), eb, x.size)
    x3 = np.asarray(ops.unpack_dequantize(packed, bw, anchor_p, eb))
    np.testing.assert_array_equal(x3, x2)


def test_bitwidth_exact_at_powers_of_two():
    """Integer bitwidth computation has no float-log edge cases."""
    for v in [0, 1, 2, 3, 4, 7, 8, 255, 256, (1 << 30) - 1, 1 << 30, (1 << 31)]:
        got = int(ref.bitwidth_of(jnp.asarray([np.uint32(v)]))[0])
        want = v.bit_length()
        assert got == want, (v, got, want)
