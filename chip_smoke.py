#!/usr/bin/env python3
"""Smoke test of the gZ compressed-collective path on TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four chips of one host

Everything goes through the public surface (``GZConfig(...).compressor()``,
``GZCommunicator``, ``GZHierCommunicator``, ``repro.launch.train``) with the
Pallas kernels compiled for the chip: no interpret mode, no CPU fallback.

One chip (the default):

* codec phase: a 16 MiB (4,194,304 f32, the default gradient bucket) smooth
  random walk and a rough Gaussian field, made on the device from
  ``--seed``, go through compress -> decompress, ``decompress_reduce`` and
  ``decompress_reduce_compress`` for the codecs lorenzo, lorenzo+entropy
  and lossless.  Checks: the error is within eb (bitwise for lossless), the
  packed words and the decoded values equal the jnp oracle
  (``core/bitpack.py``, ``core/entropy.py``, ``kernels/ref.py``), and the
  fused hop equals the two-pass composition byte for byte.  The lowered
  text of every compress, decompress and hop function holds a
  ``tpu_custom_call``.
* train phase: ``repro.launch.train`` on mamba2-780m at full width and
  depth (48 layers, d_model 1536, vocab 50280) with ``--grad-gz auto``,
  3 steps on a 1x1 mesh; the loss must be finite.

Four chips (``--chips 4``), and nothing else, in this one process:

* ``GZCommunicator`` allreduce (ring and redoub), reduce_scatter,
  allgather, broadcast and scatter on a 4-rank axis at 16 MiB per rank,
  against ``psum``, ``psum_scatter``, ``all_gather`` and the root's
  payload, within the error budgets of ``tests/_mp_*_child.py``;
* the 2x2 ``GZHierCommunicator`` allreduce against ``psum``;
* 3 data-parallel steps of the same training run on the 4 chips, with
  ``--grad-gz auto`` and without.

Any failed check raises, and the script exits non-zero.  Without a TPU, or
run outside a checkout of this repository, it exits non-zero and prints no
result.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"
N = 4 * 1024 * 1024  # f32 elements: 16 MiB, SyncConfig.bucket_bytes
EB = 1e-4
CAPACITY_FACTOR = 0.6
CODECS = ("lorenzo", "lorenzo+entropy", "lossless")
TRAIN_ARGS = ["--arch", "mamba2-780m", "--steps", "3", "--batch", "8",
              "--seq", "512", "--log-every", "1"]


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def _import_repo():
    """Import the library from the checkout this script sits in — never
    from anywhere else on the path."""
    if not (SRC / "repro" / "kernels" / "lorenzo.py").is_file():
        _fail(f"no repository sources under {SRC}")
    sys.path.insert(0, str(SRC))
    from repro.kernels import ops

    if SRC not in pathlib.Path(ops.__file__).resolve().parents:
        _fail(f"repro was imported from {ops.__file__}, not from {SRC}")


def _check(ok, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _require_kernels(name: str, fn, *args) -> None:
    """The lowered program must run its codec as Mosaic kernels."""
    text = fn.lower(*args).as_text()
    _check("tpu_custom_call" in text, f"{name}: no tpu_custom_call in the "
           "lowered program (kernels not compiled for the chip)")


def _timed(fn, *args):
    """(result, seconds of the first call incl. compile, seconds of one
    warm call) — host clock around block_until_ready."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, first, time.perf_counter() - t0


def _oracle(codec: str, comp, x, cap: int):
    """jnp reference of ``comp.compress``: (packed, bitwidth, anchor) and
    the decoded values."""
    import jax.numpy as jnp

    from repro.core import bitpack, entropy
    from repro.kernels import ops, ref

    x2d = ops.to_blocks(x)
    eb = jnp.float32(EB)
    if codec == "lorenzo":
        packed, bw, anchor = ref.quantize_pack_ref(x2d, eb, cap)
        y2d = ref.dequantize_ref(bitpack.unpack(packed, bw, comp.block), anchor, eb)
    else:
        codes, anchor = entropy.encode_blocks(x2d, eb, lossless=comp.lossless)
        packed, bw, _ = entropy.pack(codes, cap)
        y2d = entropy.decode_blocks(entropy.unpack(packed, bw, comp.block),
                                    anchor, eb, lossless=comp.lossless)
    return (packed, bw, anchor), ops.from_blocks(y2d, x.size)


def codec_phase(n: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.collectives import GZConfig

    k_walk, k_rough, k_acc = jax.random.split(jax.random.key(seed), 3)
    fields = {
        "smooth": jax.jit(lambda k: jnp.cumsum(
            jax.random.normal(k, (n,), jnp.float32) * 1e-3))(k_walk),
        "rough": jax.random.normal(k_rough, (n,), jnp.float32),
    }
    acc = jax.random.normal(k_acc, (n,), jnp.float32)
    same = lambda a, b: bool(jnp.array_equal(
        jax.lax.bitcast_convert_type(a, jnp.uint32),
        jax.lax.bitcast_convert_type(b, jnp.uint32)))
    for codec in CODECS:
        comp = GZConfig(eb=EB, capacity_factor=CAPACITY_FACTOR,
                        codec=codec).compressor()
        exact = codec == "lossless"
        compress = jax.jit(lambda x: comp.compress(x, EB))
        decompress = jax.jit(comp.decompress)
        reduce = jax.jit(comp.decompress_reduce)
        hop = jax.jit(lambda c, a: comp.decompress_reduce_compress(c, a)[0])
        two_pass = jax.jit(lambda c, a: comp.compress(
            comp.decompress_reduce(c, a), EB))
        for name, data in fields.items():
            tag = f"{codec}/{name}"
            c, t_c0, t_c = _timed(compress, data)
            y, t_d0, t_d = _timed(decompress, c)
            _require_kernels(f"{tag} compress", compress, data)
            _require_kernels(f"{tag} decompress", decompress, c)
            _require_kernels(f"{tag} decompress_reduce", reduce, c, acc)
            _require_kernels(f"{tag} hop", hop, c, acc)
            _check(not bool(c.overflowed()), f"{tag}: stream overflowed "
                   f"({int(c.nwords)} > {c.capacity_words} words)")
            (o_packed, o_bw, o_anchor), o_y = _oracle(
                codec, comp, data, c.capacity_words)
            _check(same(c.packed, o_packed) and same(c.bitwidth, o_bw)
                   and same(c.anchor, o_anchor),
                   f"{tag}: packed stream differs from the jnp oracle")
            _check(same(y, o_y), f"{tag}: decoded values differ from the oracle")
            err = float(jnp.max(jnp.abs(y - data)))
            xmax = float(jnp.max(jnp.abs(data)))
            if exact:
                _check(same(y, data), f"{tag}: lossless round trip not bitwise")
            else:
                _check(err <= EB * (1 + 1e-3) + xmax * 2e-7,
                       f"{tag}: max error {err} > eb {EB}")
            r = reduce(c, acc)
            _check(bool(jnp.allclose(r, acc + y, rtol=0, atol=1e-6)),
                   f"{tag}: decompress_reduce != acc + decompress")
            fused, two = hop(c, acc), two_pass(c, acc)
            _check(same(fused.packed, two.packed)
                   and same(fused.bitwidth, two.bitwidth)
                   and same(fused.anchor, two.anchor),
                   f"{tag}: fused hop differs from the two-pass composition")
            ratio = n / max(int(c.nwords), 1)
            print(f"codec {tag}: ok  max_err={err:.3e} eb={EB:g} "
                  f"exact={exact} stream_words={int(c.nwords)} "
                  f"capacity_words={c.capacity_words} ratio={ratio:.2f}  "
                  f"compress first={t_c0:.2f}s warm={t_c * 1e3:.2f}ms  "
                  f"decompress first={t_d0:.2f}s warm={t_d * 1e3:.2f}ms",
                  flush=True)


def train_phase(extra=()) -> list:
    import numpy as np

    from repro.launch import train as train_cli

    t0 = time.perf_counter()
    losses = train_cli.train(TRAIN_ARGS + list(extra))
    _check(len(losses) == 3 and np.isfinite(losses).all(),
           f"train {list(extra)}: losses {losses}")
    print(f"train {' '.join(TRAIN_ARGS + list(extra))}: ok losses="
          f"{[float(v) for v in losses]} ({time.perf_counter() - t0:.1f}s)",
          flush=True)
    return losses


def collectives_phase(n: int, seed: int) -> None:
    """The compressed collectives on a 4-rank axis vs their lax twins."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.collectives import GZConfig
    from repro.core.comm import GZCommunicator, GZHierCommunicator
    from repro.core.shmap import shard_map

    ranks = len(jax.devices())
    _check(ranks == 4, f"--chips 4 needs 4 devices, found {ranks}")
    auto = jax.sharding.AxisType.Auto  # plain jnp ops on the results too
    mesh = jax.make_mesh((ranks,), ("x",), axis_types=(auto,))
    rows = NamedSharding(mesh, P("x", None))

    # Per-rank smooth fields, generated in place on their chips.
    base = jax.jit(lambda k: jnp.cumsum(jax.random.normal(
        k, (ranks, n), jnp.float32) * 0.01, axis=1), out_shardings=rows)(
        jax.random.key(seed))
    # Root-significant inputs: only rank 0's row carries the payload.
    root = jax.jit(lambda b: jnp.zeros_like(b).at[0].set(b[0]),
                   out_shardings=rows)(base)
    full = jax.jit(lambda b: jnp.zeros((ranks, ranks * n), b.dtype).at[0].set(
        b.reshape(-1)), out_shardings=rows)(base)

    def run(body, x, out_spec=P("x", None), m=mesh, in_spec=P("x", None)):
        f = jax.jit(shard_map(body, mesh=m, in_specs=(in_spec,),
                              out_specs=out_spec))
        return f(x)

    def report(name, out, want, budget, ovf, plan=None, replicated=False):
        err = float(jnp.max(jnp.abs(out - want)))
        bound = EB * budget[0] + float(jnp.max(jnp.abs(want))) * budget[1]
        _check(not bool(jnp.any(ovf)), f"{name}: capacity overflow")
        _check(err <= bound, f"{name}: err {err} > budget {bound}")
        if replicated:
            _check(bool(jnp.all(out == out[0:1])),
                   f"{name}: ranks disagree")
        desc = ""
        if plan is not None:
            desc = (f" plan(algo={plan.algo} chunks={plan.pipeline_chunks} "
                    f"codec={plan.codec} wire_bytes={plan.wire_bytes} "
                    f"ratio={plan.ratio:.2f})")
        print(f"collective {name}: ok err={err:.3e} budget={bound:.3e}{desc}",
              flush=True)

    def planned(comm, op, x):
        res = getattr(comm, op)(x[0])
        return res.value[None], res.overflow[None]

    flagged = (P("x", None), P("x"))
    total = run(lambda x: lax.psum(x[0], "x")[None], base)
    for algo in ("ring", "redoub"):
        comm = GZCommunicator("x", config=GZConfig(eb=EB, algo=algo),
                              axis_size=ranks)
        out, ovf = run(lambda x: planned(comm, "allreduce", x), base,
                       out_spec=flagged)
        report(f"allreduce[{algo}]", out, total, (1.05, 1e-6), ovf,
               comm.plan("allreduce", n))

    comm = GZCommunicator("x", config=GZConfig(eb=EB), axis_size=ranks)
    out, ovf = run(lambda x: planned(comm, "reduce_scatter", x), base,
                   out_spec=flagged)
    want = run(lambda x: lax.psum_scatter(x[0], "x", tiled=True)[None], base)
    report("reduce_scatter", out, want, (1.05, 1e-6), ovf,
           comm.plan("reduce_scatter", n))

    out, ovf = run(lambda x: planned(comm, "allgather", x), base,
                   out_spec=flagged)
    want = run(lambda x: lax.all_gather(x[0], "x", tiled=True)[None], base)
    report("allgather", out, want, (1.001, 2e-7), ovf,
           comm.plan("allgather", n), replicated=True)

    out, ovf = run(lambda x: planned(comm, "broadcast", x), root,
                   out_spec=flagged)
    report("broadcast", out, jnp.broadcast_to(base[0], out.shape),
           (1.001, 2e-7), ovf, comm.plan("broadcast", n), replicated=True)

    out, ovf = run(lambda x: planned(comm, "scatter", x), full,
                   out_spec=flagged)
    report("scatter", out, base, (1.001, 2e-7), ovf,
           comm.plan("scatter", ranks * n))

    hmesh = jax.make_mesh((2, 2), ("node", "local"), axis_types=(auto,) * 2)
    hrows = P(("node", "local"), None)
    hier = GZHierCommunicator("node", "local", config=GZConfig(eb=EB),
                              topology=(2, 2))
    hbase = jax.device_put(base, NamedSharding(hmesh, hrows))

    out, ovf = run(lambda x: planned(hier, "allreduce", x), hbase,
                   out_spec=(hrows, P(("node", "local"))), m=hmesh,
                   in_spec=hrows)
    want = run(lambda x: lax.psum(x[0], ("node", "local"))[None], hbase,
               out_spec=hrows, m=hmesh, in_spec=hrows)
    hp = hier.plan(n)
    report(f"hier_allreduce[2x2 {'flat' if hp.flat else 'two-level'}]",
           out, want, (1.05, 1e-6), ovf)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip collectives and the "
                         "data-parallel training run")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _import_repo()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU found (JAX platform is {devices[0].platform!r}); "
              "this smoke test runs only on the chip")
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    _check(not ops._interpret(), "Pallas kernels would run in interpret mode")
    cache = enable_compile_cache()
    print(f"devices: {len(devices)} x {devices[0].device_kind}; "
          f"jax {jax.__version__}; compile cache {cache}", flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        collectives_phase(N, args.seed)
        t1 = time.perf_counter()
        print(f"phase collectives: {t1 - t0:.1f}s", flush=True)
        train_phase(["--grad-gz", "auto"])
        train_phase()
        print(f"phase dp_train: {time.perf_counter() - t1:.1f}s", flush=True)
    else:
        codec_phase(N, args.seed)
        t1 = time.perf_counter()
        print(f"phase codec: {t1 - t0:.1f}s", flush=True)
        train_phase(["--grad-gz", "auto"])
        print(f"phase train: {time.perf_counter() - t1:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
