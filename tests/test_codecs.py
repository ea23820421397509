"""Wire-codec registry + Pallas entropy stage (DESIGN.md §10, ISSUE 8).

Deterministic coverage of the codec subsystem:

  * registry contents/validation and the per-codec container protocol;
  * round-trip error <= eb for the lossy codecs, bit-exact round trips
    (NaN/Inf/-0.0 included) for lossless/passthrough, eb=0 semantics;
  * the entropy invariant: the per-sub-block trimmed stream is NEVER
    longer than the dense bitpack of the same codes, and strictly
    shorter on smooth data;
  * fused (Pallas) vs oracle byte identity for the entropy codec;
  * the `codec="lorenzo"` default resolves byte-identically to the
    pre-registry compressor, and `compressor.DEFAULT` still works as a
    deprecation shim;
  * plan-layer threading: Plan.codec/notes, per-codec wire accounting,
    fused-hop downgrade, intring forcing, auto selection from modeled
    and calibrated terms, cache keying + by_codec stats.

The hypothesis sweep over shapes x ebs x codecs lives in
tests/test_codecs_property.py (importorskip'd); the multi-device
equivalence legs live in tests/_mp_codecs_child.py.
"""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import codecs, comm, compressor, cost_model, entropy
from repro.core.collectives import GZConfig
from repro.kernels import ops

EB = 1e-4
# Off-block, exact-block, ragged, multi-tile: the shapes that have caught
# every padding bug in this repo so far.
SHAPES = (100, 256, 1537, 2048, 5000)


@pytest.fixture(autouse=True)
def _fresh_cache():
    comm.clear_plan_cache()
    yield
    comm.clear_plan_cache()


def _smooth(n, seed=0, scale=0.01):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.cumsum(rng.normal(0, scale, n)), jnp.float32)


def _rough(n, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(0, 100.0, n), jnp.float32
    )


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def test_registry_contents():
    names = codecs.codec_names()
    for required in ("lorenzo", "lorenzo+entropy", "lossless", "passthrough"):
        assert required in names
    # passthrough is the explicit-opt-in control codec, never auto-picked.
    assert "passthrough" not in codecs.auto_codecs()
    assert "lorenzo" in codecs.auto_codecs()


def test_registry_validation():
    with pytest.raises(ValueError, match="unknown codec"):
        codecs.get_codec("zstd")
    with pytest.raises(ValueError, match="reserved"):
        codecs.register_codec(dataclasses.replace(
            codecs.get_codec("lorenzo"), name=codecs.AUTO))
    with pytest.raises(ValueError, match="labeled"):
        codecs.register_codec(dataclasses.replace(
            codecs.get_codec("lorenzo"), name="mislabeled"))
    with pytest.raises(TypeError):
        codecs.register_codec("not-a-spec")
    with pytest.raises(ValueError, match="GZConfig.codec"):
        GZConfig(codec="zstd")
    # "auto" is a legal config value (resolved by the plan layer)...
    GZConfig(codec="auto")
    # ...but never a buildable compressor.
    with pytest.raises(ValueError, match="plan layer"):
        codecs.build_compressor("auto", capacity_factor=0.6, fused=True)


def test_register_codec_extensible():
    spec = dataclasses.replace(
        codecs.get_codec("lorenzo"), name="lorenzo2",
        terms=cost_model.CodecTerms("lorenzo2"),
    )
    codecs.register_codec(spec)
    try:
        assert "lorenzo2" in codecs.codec_names()
        comp = codecs.build_compressor(
            "lorenzo2", capacity_factor=0.6, fused=True
        )
        assert isinstance(comp, compressor.ErrorBoundedLorenzo)
    finally:
        codecs._CODECS.pop("lorenzo2", None)


def test_default_shim_is_deprecated():
    with pytest.warns(DeprecationWarning, match="codecs.build_compressor"):
        d = compressor.DEFAULT
    assert isinstance(d, compressor.ErrorBoundedLorenzo)
    with pytest.raises(AttributeError):
        compressor.NO_SUCH_NAME


# ---------------------------------------------------------------------------
# Round trips + container protocol
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", SHAPES)
@pytest.mark.parametrize("name", ("lorenzo", "lorenzo+entropy"))
def test_lossy_roundtrip_within_eb(name, n):
    comp = codecs.build_compressor(name, capacity_factor=1.2, fused=True)
    x = _smooth(n, seed=n)
    c = comp.compress(x, EB)
    assert not bool(c.overflowed())
    y = comp.decompress(c)
    assert float(jnp.max(jnp.abs(y - x))) <= EB * (1 + 1e-6)
    # The receive side can rebuild the true stream size from metadata.
    assert int(comp.stream_nwords(c.bitwidth, n)) == int(c.nwords)


@pytest.mark.parametrize("n", SHAPES)
@pytest.mark.parametrize("name", ("lossless", "passthrough"))
def test_exact_codecs_roundtrip_bitwise(name, n):
    comp = codecs.build_compressor(name, capacity_factor=1.25, fused=True)
    x = _rough(n, seed=n)
    # Exact codecs must survive every IEEE bit pattern, eb ignored.
    special = np.array([np.nan, np.inf, -np.inf, -0.0, 1e-38], np.float32)
    x = x.at[: special.size].set(jnp.asarray(special))
    c = comp.compress(x, 0.0)  # eb=0 semantics: no divide, no loss
    assert not bool(c.overflowed())
    y = comp.decompress(c)
    np.testing.assert_array_equal(
        np.asarray(x).view(np.uint32), np.asarray(y).view(np.uint32)
    )
    assert int(comp.stream_nwords(c.bitwidth, n)) == int(c.nwords)


@pytest.mark.parametrize("name", codecs.codec_names())
def test_decompress_reduce_matches_composition(name):
    comp = codecs.build_compressor(name, capacity_factor=1.25, fused=True)
    n = 1537
    x, acc = _smooth(n, seed=1), _smooth(n, seed=2)
    c = comp.compress(x, EB)
    got = comp.decompress_reduce(c, acc)
    want = acc + comp.decompress(c)
    # Fused reduce kernels fold acc + q*2eb into an FMA (one rounding);
    # the composition rounds twice — 1-ulp tolerance, not bitwise.
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
    )


# ---------------------------------------------------------------------------
# The entropy invariant: trimmed stream <= dense bitpack, always
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", SHAPES)
@pytest.mark.parametrize("eb", (1e-3, 1e-4))
@pytest.mark.parametrize("rough", (False, True))
def test_entropy_never_longer_than_dense(n, eb, rough):
    x = _rough(n, seed=n) if rough else _smooth(n, seed=n)
    # 2.0 = MAX_CAPACITY_FACTOR: rough data at small n needs the headroom
    # (the dense pack of one 19-bit block already exceeds 1.5 * n words).
    dense = codecs.build_compressor("lorenzo", capacity_factor=2.0, fused=True)
    trim = codecs.build_compressor(
        "lorenzo+entropy", capacity_factor=2.0, fused=True
    )
    cd, ct = dense.compress(x, eb), trim.compress(x, eb)
    assert not bool(cd.overflowed()) and not bool(ct.overflowed())
    assert int(ct.nwords) <= int(cd.nwords), (
        "entropy stream longer than dense bitpack — the descriptor-in-"
        "bitwidth-slot invariant is broken"
    )
    if not rough:
        assert int(ct.nwords) < int(cd.nwords), (
            "entropy stage bought nothing on smooth data"
        )
    # Identical quantization: both decode to the same grid points.
    np.testing.assert_array_equal(
        np.asarray(dense.decompress(cd)), np.asarray(trim.decompress(ct))
    )


@pytest.mark.parametrize("n", (100, 1537, 5000))
def test_entropy_fused_matches_oracle_bytes(n):
    x = _smooth(n, seed=n)
    fused = codecs.build_compressor(
        "lorenzo+entropy", capacity_factor=1.2, fused=True
    )
    oracle = dataclasses.replace(fused, fused=False)
    cf, co = fused.compress(x, EB), oracle.compress(x, EB)
    assert int(cf.nwords) == int(co.nwords)
    k = int(cf.nwords)
    np.testing.assert_array_equal(
        np.asarray(cf.packed[:k]), np.asarray(co.packed[:k])
    )
    np.testing.assert_array_equal(
        np.asarray(cf.bitwidth), np.asarray(co.bitwidth)
    )
    np.testing.assert_array_equal(
        np.asarray(cf.anchor), np.asarray(co.anchor)
    )


def test_entropy_descriptor_words_authority():
    """packed_words(desc) (the wire metadata) equals the true scatter
    extent — the receive side's stream_nwords rebuilds exactly it."""
    x = _smooth(2048, seed=9)
    comp = codecs.build_compressor(
        "lorenzo+entropy", capacity_factor=1.2, fused=True
    )
    c = comp.compress(x, EB)
    assert int(entropy.packed_words(c.bitwidth)) == int(c.nwords)
    # And the oracle geometry agrees block by block.
    codes, anchor = entropy.encode_blocks(ops.to_blocks(x), jnp.float32(EB))
    desc = entropy.make_desc(entropy.sub_widths(codes))
    np.testing.assert_array_equal(np.asarray(desc), np.asarray(c.bitwidth))


# ---------------------------------------------------------------------------
# Default-codec identity with the pre-registry path
# ---------------------------------------------------------------------------


def test_default_codec_bytes_identical_to_pre_registry_compressor():
    cfg = GZConfig()
    assert cfg.codec == "lorenzo"
    comp = cfg.compressor()
    legacy = compressor.ErrorBoundedLorenzo(
        capacity_factor=cfg.capacity_factor, fused=cfg.fused
    )
    assert comp == legacy  # frozen dataclasses: same knobs, same kernels
    x = _smooth(4096, seed=4)
    c, cl = comp.compress(x, cfg.eb), legacy.compress(x, cfg.eb)
    np.testing.assert_array_equal(np.asarray(c.packed), np.asarray(cl.packed))
    np.testing.assert_array_equal(
        np.asarray(c.bitwidth), np.asarray(cl.bitwidth)
    )


def test_capacity_authority_shared_by_plan_and_compressor():
    for name in codecs.codec_names():
        for n in SHAPES:
            cap = codecs.codec_capacity_words(name, n, 0.6)
            comp = codecs.build_compressor(
                name, capacity_factor=0.6, fused=True
            )
            c = comp.compress(_smooth(n), EB)
            assert c.packed.shape[0] == cap, (
                f"codec {name!r} at n={n}: plan provisions {cap} words, "
                f"execute ships {c.packed.shape[0]}"
            )


def test_codec_capacity_overrides():
    # lossless provisions the structural worst case (whole blocks @ BLOCK
    # words each) regardless of the factor knob — overflow is impossible.
    assert codecs.codec_capacity_words("lossless", 4096, 0.1) == 4096
    assert codecs.codec_capacity_words("lossless", 100, 0.1) == 256
    assert codecs.codec_capacity_words("lossless", 257, 0.1) == 512
    # ...passthrough provisions structurally too: exactly n words (min 8).
    assert codecs.codec_capacity_words("passthrough", 4096, 0.1) == 4096
    assert codecs.codec_capacity_words("passthrough", 3, 2.0) == 8


# ---------------------------------------------------------------------------
# Plan-layer threading
# ---------------------------------------------------------------------------


def _comm(n=8, **kw):
    kw.setdefault("config", GZConfig(eb=EB))
    return comm.GZCommunicator("x", axis_size=n, **kw)


def test_plan_carries_codec_and_config_roundtrip():
    for name in codecs.codec_names():
        p = _comm(config=GZConfig(eb=EB, codec=name)).plan("allreduce", 8192)
        assert p.codec == name
        assert p.as_config().codec == name


def test_default_plan_unchanged_by_registry():
    p = _comm().plan("allreduce", 8192)
    # no codec-resolution note: only the record of the kernels' walk
    assert p.codec == "lorenzo" and len(p.notes) == 1
    assert p.notes[0].startswith("codec walk: ")
    assert p.fused_hop is True
    # Wire accounting through the codec path is the pre-registry number.
    cap, wire, raw = comm._wire_accounting(
        "allreduce", p.algo, 8192, 8, 0.6, p.pipeline_chunks
    )
    assert (p.capacity_words, p.wire_bytes) == (cap, wire)


def test_fused_hop_downgrade_noted():
    p = _comm(config=GZConfig(eb=EB, codec="lorenzo+entropy")).plan(
        "allreduce", 8192
    )
    assert p.fused_hop is False
    assert any("fused_hop off" in note for note in p.notes)
    assert p.as_config().fused_hop is False


def test_intring_forces_dense_codec():
    p = _comm(
        policy="accuracy", config=GZConfig(eb=EB, codec="lorenzo+entropy")
    ).plan("allreduce", 8192)
    assert p.algo == "intring" and p.codec == "lorenzo"
    assert any("integer wire format" in note for note in p.notes)


def test_auto_codec_concrete_on_plan():
    p = _comm(config=GZConfig(eb=EB, codec="auto")).plan("allreduce", 8192)
    assert p.codec in codecs.auto_codecs()
    assert any("codec auto->" in note for note in p.notes)
    p.as_config().compressor()  # never raises: plans are concrete


def test_auto_codec_under_paper_policy_defaults_dense():
    p = _comm(policy="paper", config=GZConfig(eb=EB, codec="auto")).plan(
        "allreduce", 8192
    )
    assert p.codec == "lorenzo"
    assert any("does not rank" in note for note in p.notes)


def _hw_with_terms(*terms):
    return dataclasses.replace(
        cost_model.TPU_V5E, codec_terms=tuple(terms), name="synthetic"
    )


def test_auto_codec_selects_entropy_when_its_model_wins():
    # Calibrated terms say the entropy wire is 50x smaller while lorenzo
    # barely compresses: the modeled collective time must pick entropy.
    hw = _hw_with_terms(
        cost_model.CodecTerms("lorenzo", ratio_abs=1.01),
        cost_model.CodecTerms("lorenzo+entropy", ratio_abs=50.0),
        cost_model.CodecTerms("lossless", ratio_abs=1.01),
    )
    p = _comm(hw=hw, config=GZConfig(eb=EB, codec="auto")).plan(
        "allreduce", 1 << 20
    )
    assert p.codec == "lorenzo+entropy"
    assert p.codec_ratio == 50.0


def test_auto_codec_selects_dense_when_entropy_model_loses():
    hw = _hw_with_terms(
        cost_model.CodecTerms("lorenzo+entropy", ratio_abs=1.01),
        cost_model.CodecTerms("lossless", ratio_abs=1.01),
    )
    p = _comm(hw=hw, config=GZConfig(eb=EB, codec="auto")).plan(
        "allreduce", 1 << 20
    )
    assert p.codec == "lorenzo"


def test_calibrated_terms_override_registry_defaults():
    hw = _hw_with_terms(cost_model.CodecTerms("lorenzo+entropy",
                                              ratio_abs=7.0))
    p = _comm(hw=hw, config=GZConfig(eb=EB, codec="lorenzo+entropy")).plan(
        "allreduce", 8192
    )
    assert p.codec_ratio == 7.0  # not the registry's ratio_scale model


# ---------------------------------------------------------------------------
# Cache keying + by_codec stats (satellite: one entry per (op, codec))
# ---------------------------------------------------------------------------


def test_one_cache_entry_per_op_codec():
    for name in ("lorenzo", "lorenzo+entropy", "lossless"):
        c = _comm(config=GZConfig(eb=EB, codec=name))
        for _ in range(3):
            c.plan("allreduce", 8192)
            c.plan("scatter", 8192)
    s = comm.plan_cache_stats()
    assert s["entries"] == 6  # 2 ops x 3 codecs
    per_op_codec = {(k[0], k[-1]) for k in s["keys"]}
    assert len(per_op_codec) == 6, "duplicate (op, codec) cache entries"
    for name in ("lorenzo", "lorenzo+entropy", "lossless"):
        rec = s["by_codec"][name]
        assert rec == {"hits": 4, "misses": 2, "entries": 2,
                       "hier_entries": 0}


def test_by_codec_includes_hier_cache():
    h = comm.GZHierCommunicator(
        "n", "l", topology=(2, 4), config=GZConfig(eb=EB, codec="lossless")
    )
    h.plan(1 << 14)
    h.plan(1 << 14)
    rec = comm.plan_cache_stats()["by_codec"]["lossless"]
    assert rec["hier_entries"] == 1
    assert rec["hits"] >= 1  # the second plan() call hit
    # Hier sub-plans resolve through the flat cache under the same codec.
    assert rec["entries"] >= 1


def test_codec_key_appended_last():
    """The child test pins key[:5]; the by_codec stats read key[-1]."""
    _comm(config=GZConfig(eb=EB, codec="lossless")).plan("allreduce", 8192)
    (k,) = comm.plan_cache_stats()["keys"]
    assert k[:5] == ("allreduce", 8192 * 4, "float32", 8, EB)
    assert k[-1] == "lossless"


def test_clear_resets_by_codec():
    _comm().plan("allreduce", 8192)
    comm.clear_plan_cache()
    assert comm.plan_cache_stats()["by_codec"] == {}


# ---------------------------------------------------------------------------
# Calibration
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_measure_and_fit_codec_terms():
    measured = comm.measure_codecs(
        GZConfig(eb=EB), sizes=(4096, 16384), reps=1
    )
    assert set(measured) == set(codecs.codec_names())
    for name, m in measured.items():
        assert m["ratio"] > 0
        assert len(m["samples_compress"]) == 2
    # Smooth data: the entropy trim must beat the dense bitpack.
    assert measured["lorenzo+entropy"]["ratio"] > measured["lorenzo"]["ratio"]
    hw = comm.fit_codec_terms(measured, base=cost_model.TPU_V5E)
    fitted = {t.codec for t in hw.codec_terms}
    assert fitted == set(codecs.codec_names())
    for t in hw.codec_terms:
        spec = codecs.get_codec(t.codec)
        if spec.eb_scaled:
            assert t.ratio_abs == 0.0 and t.ratio_scale > 0
        else:
            assert t.ratio_abs >= 1.0
    # The fitted entropy scale must exceed dense's (strictly better wire).
    scale = {t.codec: t.ratio_scale for t in hw.codec_terms}
    assert scale["lorenzo+entropy"] > scale["lorenzo"]
    # And the planner consumes them: terms_for resolves the fitted entry.
    assert hw.terms_for("lorenzo+entropy").ratio_scale == \
        scale["lorenzo+entropy"]
    assert hw.terms_for("nope") is None
