"""Bytes behind the rooflines and model FLOPs, on hand-computed cases."""
import json

import pytest

from bench import harness, work


def test_stream_and_round_trip_bytes():
    # 512 values = 2 blocks of 256: 100 words + 2 * (bitwidth + anchor)
    assert work.stream_bytes(100, 512) == 400 + 16
    # 2 * (512 * 4 + 416)
    assert work.codec_roundtrip_bytes(512, 100) == 2 * (2048 + 416)


def test_ring_allreduce_bytes_by_hand():
    # n = 1024 on 4 ranks: chunks of 256 (one block), 10 words a stream
    s = 40 + 8          # one stream
    c = 256 * 4         # one f32 chunk
    want = (c + s) + 3 * (2 * s + c) + c + 3 * (s + c)
    assert work.ring_allreduce_bytes(1024, 4, 10) == want
    # two ranks: one hop, one gathered stream
    assert work.ring_allreduce_bytes(512, 2, 10) == (c + s) + (2 * s + c) + c + (s + c)


def test_least_seconds_uses_the_peak():
    peaks = harness.load_peaks("TPU v5 lite")
    assert work.least_seconds(819e9, peaks) == pytest.approx(1.0)


MAMBA2 = "mamba2-780m-untied-bf16res"


def _mamba2_ref():
    return harness.load_module(harness.BENCH / "configs" / f"{MAMBA2}.ref.py",
                               "ref")


def test_mamba2_flops_by_hand():
    ref = _mamba2_ref()
    cfg = {"d_model": 4, "expand": 2, "d_state": 2, "headdim": 4,
           "chunk_size": 2, "d_conv": 3, "vocab_size": 10, "n_layer": 2}
    # di 8, heads 2; per layer: in 2*4*(16+4+2)=176, conv 2*3*(8+4)=72,
    # diag 2*2*1 + 2*2*4*1 = 20, state 2*(2*2*4*2) = 64, out 2*8*4 = 64
    layer = 176 + 72 + 20 + 64 + 64
    assert ref.forward_flops_per_token(cfg) == 2 * layer + 2 * 4 * 10
    assert ref.flops_per_token(cfg) == 3 * (2 * layer + 80)


def test_mamba2_780m_is_about_six_flops_a_parameter():
    cfg = json.loads((harness.BENCH / "configs" / f"{MAMBA2}.json").read_text())
    # 853M parameters (embedding lookups do no FLOPs, the SSD adds some)
    per_token = _mamba2_ref().flops_per_token(cfg)
    assert 4.5e9 < per_token < 5.5e9
