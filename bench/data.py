"""Seeded inputs, made on the device.

The smooth random walk and the Gaussian field are the generators of
``chip_smoke.py`` (``codec_phase``), kept here so that the benchmark's
inputs cannot change with the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

WALK_STEP = 1e-3


def key(seed: int):
    """A PRNG key from any whole number, also one wider than 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def smooth_walk(k, shape, step: float = WALK_STEP):
    """cumsum(N(0, 1) * step) along the last axis: a smooth field."""
    return jnp.cumsum(jax.random.normal(k, shape, jnp.float32) * step, axis=-1)


def gaussian(k, shape):
    return jax.random.normal(k, shape, jnp.float32)


FIELDS = {"smooth_walk": smooth_walk, "gaussian": gaussian}


class TokenStream:
    """Seeded token batches: a Zipf-like unigram mix with next-token labels.

    A copy of the program's ``SyntheticStream`` / ``make_batch``
    (``repro/data/pipeline.py``) for decoder-only models: ranks 1..V with
    p ~ 1 / rank**1.1, (batch, seq + 1) draws, tokens and labels shifted by
    one. Every call gives new rows.
    """

    def __init__(self, vocab: int, batch: int, seq: int, seed: int):
        import numpy as np

        self.batch, self.seq = batch, seq
        self.rng = np.random.default_rng(int(seed))
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        p = 1.0 / ranks ** 1.1
        self.p = p / p.sum()
        self.vocab = vocab

    def __next__(self) -> dict:
        import numpy as np

        toks = self.rng.choice(self.vocab, size=(self.batch, self.seq + 1),
                               p=self.p).astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:].copy()}
