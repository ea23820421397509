"""Compile the codec kernels and a 4-chip compressed allreduce for a
described TPU v5e (no chip attached).

Nothing runs: these tests show that Mosaic and XLA accept the programs at
the real bucket size (4,194,304 f32 = the 16 MiB default gradient bucket,
eb 1e-4, capacity factor 0.6), which interpret mode on the CPU cannot.
``ops._interpret`` is steered to the chip path with ``monkeypatch``.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports every test file.  The persistent compilation cache is
turned off around the compiles (an entry written for a described chip
cannot be read back without one).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.collectives import GZConfig
from repro.core.compressed import capacity_words_for
from repro.core.compressor import (COMPRESS, DECOMPRESS, HOP,
                                   lossless_capacity_words)
from repro.kernels import ops

import _scopes

N = 4 * 1024 * 1024
NB = N // ops.BLOCK
EB = 1e-4
CAP = capacity_words_for(N, 0.6, ops.BLOCK)
LOSSLESS_CAP = lossless_capacity_words(N)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def chip(topo, no_compile_cache, monkeypatch):
    """ShapeDtypeStruct factory on one described chip, kernels not
    interpreted."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)


def _kernel_cases():
    x, eb = ((NB, ops.BLOCK), jnp.float32), ((), jnp.float32)
    codes = ((NB, ops.BLOCK), jnp.uint32)
    col = ((NB,), jnp.int32)
    words = ((CAP,), jnp.uint32)
    lwords = ((LOSSLESS_CAP,), jnp.uint32)
    cases = {
        "quantize": (ops.quantize, (x, eb)),
        "dequantize": (ops.dequantize, (codes, col, eb)),
        "dequantize_reduce": (ops.dequantize_reduce, (codes, col, eb, x)),
        "quantize_pack": (lambda a, e: ops.quantize_pack(a, e, CAP), (x, eb)),
        "unpack_dequantize": (ops.unpack_dequantize, (words, col, col, eb)),
        "unpack_dequantize_reduce": (ops.unpack_dequantize_reduce,
                                     (words, col, col, eb, x)),
    }
    for emit in (False, True):
        cases[f"unpack_reduce_repack[emit_f32={emit}]"] = (
            lambda p, b, a, e, acc, emit=emit: ops.unpack_reduce_repack(
                p, b, a, e, acc, e, CAP, emit_f32=emit),
            (words, col, col, eb, x))
    for ll in (False, True):
        cap, w = (LOSSLESS_CAP, lwords) if ll else (CAP, words)
        cases[f"entropy_quantize_pack[lossless={ll}]"] = (
            lambda a, e, cap=cap, ll=ll: ops.entropy_quantize_pack(
                a, e, cap, lossless=ll), (x, eb))
        cases[f"entropy_unpack_dequantize[lossless={ll}]"] = (
            lambda p, d, a, e, ll=ll: ops.entropy_unpack_dequantize(
                p, d, a, e, lossless=ll), (w, col, col, eb))
        cases[f"entropy_unpack_dequantize_reduce[lossless={ll}]"] = (
            lambda p, d, a, e, acc, ll=ll: ops.entropy_unpack_dequantize_reduce(
                p, d, a, e, acc, lossless=ll), (w, col, col, eb, x))
    return cases


KERNELS = _kernel_cases()


@pytest.mark.parametrize("name", list(KERNELS))
def test_codec_kernel_compiles_for_v5e(chip, name):
    assert ops.rows_per_step(NB) == 128  # the stream kernels' widest walk
    fn, args = KERNELS[name]
    compiled = jax.jit(fn).lower(*(chip(*a) for a in args)).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_four_chip_allreduce_compiles_for_v5e(topo, no_compile_cache,
                                              monkeypatch):
    """One GZCommunicator.allreduce program over a 4-device mesh: the
    codec runs as Mosaic kernels between collective-permutes."""
    from repro.core.comm import GZCommunicator
    from repro.core.shmap import shard_map

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices), ("x",))
    comm = GZCommunicator("x", config=GZConfig(eb=EB), axis_size=4)
    body = lambda x: comm.allreduce(x[0]).value[None]
    step = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("x", None),),
                             out_specs=P("x", None)))
    x = jax.ShapeDtypeStruct((4, N), jnp.float32,
                             sharding=NamedSharding(mesh, P("x", None)))
    text = step.lower(x).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


CODEC_SCOPES = (COMPRESS, HOP, DECOMPRESS)


def test_ring_allreduce_kernels_sit_in_codec_scopes(topo, no_compile_cache,
                                                   monkeypatch):
    """The program names its codec layer: in the compiled 4-chip ring
    allreduce every Pallas kernel has an outermost codec scope, and
    compress, hop and decompress all run."""
    from repro.core.comm import GZCommunicator
    from repro.core.shmap import shard_map

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices), ("x",))
    comm = GZCommunicator("x", config=GZConfig(
        eb=EB, algo="ring", fused_hop=True, capacity_factor=0.6), axis_size=4)
    body = lambda x: comm.allreduce(x[0]).value[None]
    step = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("x", None),),
                             out_specs=P("x", None)))
    x = jax.ShapeDtypeStruct((4, N), jnp.float32,
                             sharding=NamedSharding(mesh, P("x", None)))
    instrs = _scopes.op_names(step.lower(x).compile().as_text())
    kernels = {name: op_name for name, (op_name, rhs) in instrs.items()
               if 'custom_call_target="tpu_custom_call"' in rhs}
    assert kernels
    outermost = {}
    for name, op_name in kernels.items():
        scopes = [s for s, _ in _scopes.path(op_name) if s in CODEC_SCOPES]
        assert scopes, (name, op_name)
        outermost[name] = scopes[0]
    assert set(outermost.values()) == set(CODEC_SCOPES), outermost


def test_rows_per_step():
    """TILE_ROWS times the largest power of two <= 16 that divides the
    tile count: 8 rows for one tile or an odd count, up to 128."""
    got = {nb: ops.rows_per_step(nb)
           for nb in (8, 16, 24, 32, 64, 96, 128, 256, 1032, NB)}
    assert got == {8: 8, 16: 16, 24: 8, 32: 32, 64: 64, 96: 32, 128: 128,
                   256: 128, 1032: 8, NB: 128}


def test_allreduce_cell_plan_notes_its_walk():
    """The plan of the benchmark's allreduce cell (16,777,216 f32 per rank
    on a 4-rank ring, each piece of the 16 MiB chunk) records that its
    stream kernels walk 128 block rows per grid step."""
    from repro.core.comm import GZCommunicator

    comm = GZCommunicator("x", config=GZConfig(
        eb=EB, codec="lorenzo", capacity_factor=0.6, fused_hop=True,
        algo="ring", on_overflow="flag"), axis_size=4)
    plan = comm.plan("allreduce", 4 * N)
    nb = ops.n_blocks_for(N // plan.pipeline_chunks)
    walks = [n for n in plan.notes if n.startswith("codec walk: ")]
    assert walks == [
        f"codec walk: 128 block rows per grid step ({nb} blocks a stream)"]
