"""Compressed allreduce across the cell's chips: ``GZCommunicator.allreduce``
over one mesh axis, one call in flight.

Traffic keys: ``elements_per_rank``, ``field`` (a generator of
``bench/data.py``, different on every rank), ``pool`` distinct inputs made
from the seed and used in turn, ``sample`` answers kept from the window
(chosen from the seed) and compared with the plain reference once the
window has closed, ``baseline_seconds`` of ``lax.psum`` on the same data
after the window, printed as the baseline.
"""
from __future__ import annotations

import statistics
import time

import jax.numpy as jnp

from bench import data, harness, trace, work
from bench.drivers.codec import Sampler, gz_config

AXIS = "x"


def make_mesh(devices):
    import numpy as np
    from jax.sharding import AxisType, Mesh

    return Mesh(np.array(devices), (AXIS,), axis_types=(AxisType.Auto,))


def make_inputs(traffic: dict, seed: int, mesh):
    """The pool of (ranks, n) inputs, each rank's row made on its chip."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    ranks = mesh.devices.size
    n, k, field = traffic["elements_per_rank"], traffic["pool"], traffic["field"]
    gen = data.FIELDS[field]
    rows = NamedSharding(mesh, P(AXIS, None))
    make = jax.jit(lambda key: tuple(gen(kk, (ranks, n)) for kk in
                                     jax.random.split(key, k)),
                   out_shardings=(rows,) * k)
    return jax.block_until_ready(make(data.key(seed)))


def control(ctx: harness.Context) -> list:
    """The exact sum computed in bfloat16, put in the program's place."""
    import jax

    ref = harness.reference_module(ctx.cell, ctx.root)
    eb = float(ctx.cell.config["gz"]["eb"])
    dev0 = jax.sharding.SingleDeviceSharding(ctx.devices[0])
    worst = 0.0
    for x in make_inputs(ctx.cell.traffic, ctx.seed, make_mesh(ctx.devices)):
        x = jax.device_put(x, dev0)
        got = ref.allreduce_sum(x, jax.numpy.bfloat16)
        worst = max(worst, float(ref.max_abs_gap(got, ref.allreduce_sum(x))) / eb)
    limit = float(ctx.cell.config["limits"]["allreduce_err_over_eb"])
    return [harness.Check("allreduce_err_over_eb", worst, limit)]


class Driver:
    def __init__(self, ctx: harness.Context):
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from repro.core.comm import GZCommunicator
        from repro.core.shmap import shard_map

        self.ctx = ctx
        cfg, tr = ctx.cell.config, ctx.cell.traffic
        self.eb = float(cfg["gz"]["eb"])
        self.n = int(tr["elements_per_rank"])
        self.setup_parts = {}
        self.mesh = make_mesh(ctx.devices)
        self.ranks = self.mesh.devices.size
        t = time.perf_counter()
        self.xs = make_inputs(tr, ctx.seed, self.mesh)
        self.setup_parts["init"] = time.perf_counter() - t

        t = time.perf_counter()
        gz = gz_config(cfg)
        self.comm = GZCommunicator(AXIS, config=gz, axis_size=self.ranks)
        self.plan = self.comm.plan("allreduce", self.n)
        seen = {}

        def body(x):
            res = self.comm.allreduce(x[0])
            seen["wire_bytes"] = res.wire_bytes
            return res.value[None], res.overflow[None]

        rows = P(AXIS, None)
        self.fn = jax.jit(shard_map(body, mesh=self.mesh, in_specs=(rows,),
                                    out_specs=(rows, P(AXIS)))).lower(
            self.xs[0]).compile()
        self.wire_bytes = seen["wire_bytes"]
        self.psum = jax.jit(shard_map(lambda x: lax.psum(x[0], AXIS)[None],
                                      mesh=self.mesh, in_specs=(rows,),
                                      out_specs=rows)).lower(
            self.xs[0]).compile()
        self._cats = trace.categories_from_hlo(self.fn.as_text())
        comp = gz.compressor()
        self.block = comp.block
        chunk = -(-self.n // self.ranks)
        eb_stage = self.plan.eb_stage

        def stream_words(x):
            row = jnp.pad(x[0], (0, chunk * self.ranks - self.n))
            return jnp.stack([
                comp.compress(row[c * chunk:(c + 1) * chunk], eb_stage).nwords
                for c in range(self.ranks)])[None]

        self._stream_words = jax.jit(shard_map(
            stream_words, mesh=self.mesh, in_specs=(rows,),
            out_specs=rows)).lower(self.xs[0]).compile()
        self.setup_parts["compile"] = time.perf_counter() - t

        t = time.perf_counter()
        self.sampler = None
        self.chunk_nwords = self._chunk_nwords()
        for i in range(2 * len(self.xs)):
            self.call(i)
        jax.block_until_ready(self.psum(self.xs[0]))
        self.sampler = Sampler(int(tr["sample"]), ctx.seed)
        self.setup_parts["warmup"] = time.perf_counter() - t

    def _chunk_nwords(self) -> float:
        """Mean stream words of the ranks' chunks at the plan's per-stage
        bound: the size of the streams the ring ships, read from the seed's
        own data (every chunk of every rank's first input)."""
        return float(jnp.mean(self._stream_words(self.xs[0]).astype(
            jnp.float32)))

    def call(self, i: int) -> None:
        x = self.xs[i % len(self.xs)]
        with harness.annotate("dispatch"):
            out, ovf = self.fn(x)
        with harness.annotate("block"):
            out.block_until_ready()
        if self.sampler is not None:
            self.sampler.offer(i, (out, ovf))

    def op_categories(self) -> dict:
        return self._cats

    def end_to_end(self, latencies, window_s: float) -> dict:
        lat_ms = sorted(v * 1e3 for v in latencies)
        p95 = statistics.quantiles(lat_ms, n=20)[-1] if len(lat_ms) > 1 \
            else lat_ms[0]
        return {"allreduce_GBps": len(latencies) * self.n * 4 / window_s / 1e9,
                "allreduce_ms_p95": p95}

    def counters(self) -> dict:
        return {"elements_per_rank": self.n, "ranks": self.ranks,
                "wire_bytes": self.wire_bytes,
                "chunk_nwords": self.chunk_nwords,
                "least_bytes": work.ring_allreduce_bytes(
                    self.n, self.ranks, self.chunk_nwords, self.block)}

    def _baseline(self) -> None:
        import jax

        secs = float(self.ctx.cell.traffic.get("baseline_seconds", 1.0))
        lat = []
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < secs:
            t = time.perf_counter()
            jax.block_until_ready(self.psum(self.xs[i % len(self.xs)]))
            lat.append(time.perf_counter() - t)
            i += 1
        med = statistics.median(lat)
        self.ctx.say(f"baseline lax.psum: calls={len(lat)} "
                     f"ms_median={med * 1e3:.4f} "
                     f"GBps={self.n * 4 / med / 1e9:.3f}")

    def finish(self) -> harness.Outcome:
        import jax

        p = self.plan
        self.ctx.say(f"plan: algo={p.algo} chunks={p.pipeline_chunks} "
                     f"codec={p.codec} wire_bytes={p.wire_bytes} "
                     f"ratio={p.ratio:.4f} eb_stage={p.eb_stage:.6g} "
                     f"result_wire_bytes={self.wire_bytes} "
                     f"chunk_stream_words={self.chunk_nwords:.1f}")
        self._baseline()
        del self.fn, self.psum
        ref = harness.reference_module(self.ctx.cell, self.ctx.root)
        limit = float(self.ctx.cell.config["limits"]["allreduce_err_over_eb"])
        dev0 = jax.sharding.SingleDeviceSharding(self.ctx.devices[0])
        errs, overflowed = [], 0
        for i, (out, ovf) in self.sampler.kept:
            x = jax.device_put(self.xs[i % len(self.xs)], dev0)
            want = ref.allreduce_sum(x)
            got = jax.device_put(out, dev0)
            errs.append(float(ref.max_abs_gap(got, want[None])) / self.eb)
            overflowed += int(jax.numpy.any(ovf))
            del x, want, got
        worst = max(errs)
        self.ctx.say(f"allreduce: max_err={worst * self.eb:.6e} eb={self.eb:g} "
                     f"overflowed_calls={overflowed} "
                     f"sampled_calls={[i for i, _ in self.sampler.kept]}")
        failed = sum(e > limit for e in errs)
        return harness.Outcome(
            checks=[harness.Check("allreduce_err_over_eb", worst, limit)],
            failed=failed)
