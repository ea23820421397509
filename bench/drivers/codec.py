"""Codec round trips on one chip: ``compress`` then ``decompress`` of the
configuration's compressor, one round trip in flight.

Traffic keys: ``elements`` per round trip, ``field`` (a generator of
``bench/data.py``), ``pool`` distinct inputs made from the seed and used
in turn, ``sample`` answers kept from the window (chosen from the seed)
and compared with the plain reference once the window has closed.
"""
from __future__ import annotations

import random
import time

from bench import data, harness, trace, work


def gz_config(cfg: dict):
    from repro.core.collectives import GZConfig

    g = cfg["gz"]
    return GZConfig(eb=g["eb"], codec=g["codec"],
                    capacity_factor=g["capacity_factor"],
                    fused_hop=g["fused_hop"], algo=g["algo"],
                    on_overflow=g["on_overflow"])


def make_inputs(traffic: dict, seed: int, device):
    """The pool of seeded inputs, made on ``device`` in one call."""
    import jax
    from jax.sharding import SingleDeviceSharding

    n, k, field = traffic["elements"], traffic["pool"], traffic["field"]
    gen = data.FIELDS[field]
    one = SingleDeviceSharding(device)
    make = jax.jit(lambda key: tuple(gen(kk, (n,)) for kk in
                                     jax.random.split(key, k)),
                   out_shardings=(one,) * k)
    return jax.block_until_ready(make(data.key(seed)))


class Sampler:
    """Keeps a uniform sample of ``size`` answers (reservoir sampling with
    a generator seeded from the run's seed)."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.rng = random.Random(seed)
        self.kept = []

    def offer(self, i: int, item) -> None:
        if len(self.kept) < self.size:
            self.kept.append((i, item))
            return
        j = self.rng.randrange(i + 1)
        if j < self.size:
            self.kept[j] = (i, item)


def control(ctx: harness.Context) -> list:
    """The exact round trip held in bfloat16, put in the program's place."""
    import jax.numpy as jnp

    ref = harness.reference_module(ctx.cell, ctx.root)
    eb = float(ctx.cell.config["gz"]["eb"])
    xs = make_inputs(ctx.cell.traffic, ctx.seed, ctx.devices[0])
    worst = max(float(ref.max_abs_gap(ref.roundtrip(x, jnp.bfloat16),
                                      ref.roundtrip(x))) / eb for x in xs)
    limit = float(ctx.cell.config["limits"]["roundtrip_err_over_eb"])
    return [harness.Check("roundtrip_err_over_eb", worst, limit)]


class Driver:
    def __init__(self, ctx: harness.Context):
        import jax

        self.ctx = ctx
        cfg, tr = ctx.cell.config, ctx.cell.traffic
        self.eb = float(cfg["gz"]["eb"])
        self.n = int(tr["elements"])
        self.setup_parts = {}
        t = time.perf_counter()
        self.xs = make_inputs(tr, ctx.seed, ctx.devices[0])
        self.setup_parts["init"] = time.perf_counter() - t

        t = time.perf_counter()
        comp = gz_config(cfg).compressor()
        self.block = comp.block
        eb = self.eb
        self.compress = jax.jit(lambda x: comp.compress(x, eb)).lower(
            self.xs[0]).compile()
        c0 = self.compress(self.xs[0])
        self.decompress = jax.jit(comp.decompress).lower(c0).compile()
        self._cats = trace.categories_from_hlo(self.compress.as_text())
        self._cats.update(trace.categories_from_hlo(self.decompress.as_text()))
        self.setup_parts["compile"] = time.perf_counter() - t

        t = time.perf_counter()
        self.sampler = None
        streams = [self.compress(x) for x in self.xs]
        self.nwords = [int(c.nwords) for c in streams]
        self.capacity_words = streams[0].capacity_words
        for i in range(2 * len(self.xs)):
            self.call(i)
        self.sampler = Sampler(int(tr["sample"]), ctx.seed)
        self.setup_parts["warmup"] = time.perf_counter() - t

    def call(self, i: int) -> None:
        x = self.xs[i % len(self.xs)]
        with harness.annotate("dispatch"):
            y = self.decompress(self.compress(x))
        with harness.annotate("block"):
            y.block_until_ready()
        if self.sampler is not None:
            self.sampler.offer(i, y)

    def op_categories(self) -> dict:
        return self._cats

    def end_to_end(self, latencies, window_s: float) -> dict:
        return {"codec_GBps": len(latencies) * self.n * 4 / window_s / 1e9}

    def counters(self) -> dict:
        nwords = sum(self.nwords) / len(self.nwords)
        return {"elements": self.n, "nwords": nwords,
                "least_bytes": work.codec_roundtrip_bytes(
                    self.n, nwords, self.block)}

    def finish(self) -> harness.Outcome:
        ref = harness.reference_module(self.ctx.cell, self.ctx.root)
        limit = float(self.ctx.cell.config["limits"]["roundtrip_err_over_eb"])
        kept = self.sampler.kept
        errs = []
        for i, y in kept:
            x = self.xs[i % len(self.xs)]
            errs.append(float(ref.max_abs_gap(y, ref.roundtrip(x))) / self.eb)
        worst = max(errs)
        raw = 4 * self.n
        stream = work.stream_bytes(sum(self.nwords) / len(self.nwords),
                                   self.n, self.block)
        self.ctx.say(
            f"codec: ratio={raw / stream:.4f} (f32 bytes / stream bytes) "
            f"nwords={self.nwords} capacity_words={self.capacity_words} "
            f"max_err={worst * self.eb:.6e} eb={self.eb:g} "
            f"sampled_calls={[i for i, _ in kept]}")
        failed = sum(e > limit for e in errs)
        return harness.Outcome(
            checks=[harness.Check("roundtrip_err_over_eb", worst, limit)],
            failed=failed)
