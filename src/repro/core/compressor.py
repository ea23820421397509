"""Compressor implementations.

``ErrorBoundedLorenzo`` is the gZCCL compressor (cuSZp adapted to TPU —
Pallas quantize/dequantize kernels + dense bitpack).  ``EntropyLorenzo``
keeps the same quantizer but entropy-codes the codes at per-sub-block
widths (DESIGN.md §10); with ``lossless=True`` the quantizer becomes a
bit-exact int32 bitcast (eb=0 semantics).  ``Passthrough`` ships raw f32
bit patterns in the same wire container.  ``FixedRate`` is the [30]-style
1D fixed-rate baseline whose flaw (unbounded error under clamping) the
paper calls out; it exists so the benchmarks can reproduce that
comparison.  All share the ``Compressed`` wire container so the
collective layer is compressor-agnostic.

Compressor instances are resolved from the plan's codec entry via
``repro.core.codecs`` — the old mutable module global ``DEFAULT`` is
deprecated (see module ``__getattr__``).
"""
from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp

from repro.core import bitpack
from repro.core import entropy
from repro.core.compressed import Compressed, capacity_words_for
from repro.kernels import ops

# The codec layer's named scopes.  They are op metadata in the compiled HLO
# (``metadata={op_name=...}``), so a device profile can time each codec
# operation whatever its kernels are called; an op belongs to the outermost
# of them on its path (a two-pass hop's compress is hop time).
COMPRESS = "gz.compress"      # f32 -> wire stream
HOP = "gz.hop"                # one reduce hop, fused or two-pass
DECOMPRESS = "gz.decompress"  # wire stream -> f32


@dataclasses.dataclass(frozen=True)
class ErrorBoundedLorenzo:
    """Error-bounded block-Lorenzo compressor (the gZCCL default).

    Guarantee: |x - decompress(compress(x, eb))| <= eb element-wise, as long
    as |x|/(2*eb) < 2**30 (pre-quantization fits int32 — same envelope as
    cuSZp; asserted in tests).

    ``fused=True`` (default) runs the single-pass Pallas pipeline
    (quantize_pack / unpack_dequantize_reduce, DESIGN.md §3): the uint32
    codes array never materializes and the separate jnp bitpack pass is
    gone.  ``fused=False`` is the two-pass composition kept as the oracle
    path; both produce byte-identical wire streams.
    """

    capacity_factor: float = 0.5
    block: int = ops.BLOCK
    fused: bool = True

    @jax.named_scope(COMPRESS)
    def compress(self, x: jnp.ndarray, eb) -> Compressed:
        n = int(x.size)
        eb = jnp.asarray(eb, jnp.float32)
        x2d = ops.to_blocks(x)
        cap = capacity_words_for(n, self.capacity_factor, self.block)
        if self.fused:
            packed, bw, anchor = ops.quantize_pack(x2d, eb, cap)
            nwords = bitpack.packed_words(bw, self.block)
        else:
            codes, bw, anchor = ops.quantize(x2d, eb)
            packed, nwords = bitpack.pack(codes, bw, cap)
        return Compressed(
            packed=packed, bitwidth=bw, anchor=anchor, nwords=nwords, eb=eb,
            n=n, block=self.block,
        )

    def stream_nwords(self, bitwidth: jnp.ndarray, n: int) -> jnp.ndarray:
        """True stream words implied by wire metadata (receive-side rebuild)."""
        del n
        return bitpack.packed_words(bitwidth, self.block)

    @jax.named_scope(DECOMPRESS)
    def decompress(self, c: Compressed) -> jnp.ndarray:
        if self.fused:
            x2d = ops.unpack_dequantize(c.packed, c.bitwidth, c.anchor, c.eb)
        else:
            codes = bitpack.unpack(c.packed, c.bitwidth, c.block)
            x2d = ops.dequantize(codes, c.anchor, c.eb)
        return ops.from_blocks(x2d, c.n)

    @jax.named_scope(HOP)
    def decompress_reduce(self, c: Compressed, acc: jnp.ndarray) -> jnp.ndarray:
        """acc + decompress(c) without materializing the decompressed array.

        ``acc`` is flat (n,); fused Pallas kernel works on the padded block
        view.
        """
        acc2d = ops.to_blocks(acc)
        if self.fused:
            out2d = ops.unpack_dequantize_reduce(
                c.packed, c.bitwidth, c.anchor, c.eb, acc2d
            )
        else:
            codes = bitpack.unpack(c.packed, c.bitwidth, c.block)
            out2d = ops.dequantize_reduce(codes, c.anchor, c.eb, acc2d)
        return ops.from_blocks(out2d, c.n)

    @jax.named_scope(HOP)
    def decompress_reduce_compress(
        self, c: Compressed, acc: jnp.ndarray, eb_out=None, *,
        return_updated: bool = False,
    ):
        """Single-pass ring hop: ``compress(acc + decompress(c))`` in ONE
        Pallas kernel (DESIGN.md §3.1) — the received wire stream plus the
        local f32 chunk go in, the *next hop's* wire stream comes out, and
        the updated f32 chunk never leaves VMEM.

        ``acc`` is flat (n,) with ``n == c.n``; ``eb_out`` defaults to the
        incoming stream's bound (ring/redoub hops reuse one stage budget).
        Returns ``(Compressed, updated | None)``: ``updated`` (the plain
        f32 accumulator) is materialized only when ``return_updated`` —
        the recursive-doubling carry needs it; ring hops do not.

        ``fused=False`` runs the decompress_reduce ∘ compress composition
        (the PR 1 two-kernel path, kept as the oracle); both produce
        byte-identical wire streams.
        """
        assert int(acc.size) == c.n, (acc.size, c.n)
        eb_out = c.eb if eb_out is None else jnp.asarray(eb_out, jnp.float32)
        if not self.fused:
            updated = self.decompress_reduce(c, acc)
            return self.compress(updated, eb_out), (
                updated if return_updated else None
            )
        cap = capacity_words_for(c.n, self.capacity_factor, self.block)
        acc2d = ops.to_blocks(acc)
        res = ops.unpack_reduce_repack(
            c.packed, c.bitwidth, c.anchor, c.eb, acc2d, eb_out, cap,
            emit_f32=return_updated,
        )
        packed, bw, anchor = res[:3]
        c_out = Compressed(
            packed=packed, bitwidth=bw, anchor=anchor,
            nwords=bitpack.packed_words(bw, self.block), eb=eb_out,
            n=c.n, block=self.block,
        )
        updated = ops.from_blocks(res[3], c.n) if return_updated else None
        return c_out, updated


@dataclasses.dataclass(frozen=True)
class FixedRate:
    """1D fixed-rate baseline (ZFP-in-[30] analog): constant bits/element.

    Codes that exceed the rate are CLAMPED, so the error is unbounded —
    exactly the failure mode the paper's accuracy-aware design avoids.  The
    wire size is pre-known (the one advantage of fixed-rate).
    """

    rate_bits: int = 8
    block: int = ops.BLOCK

    @jax.named_scope(COMPRESS)
    def compress(self, x: jnp.ndarray, eb) -> Compressed:
        n = int(x.size)
        eb = jnp.asarray(eb, jnp.float32)
        x2d = ops.to_blocks(x)
        codes, _, anchor = ops.quantize(x2d, eb)
        limit = jnp.uint32((1 << self.rate_bits) - 1)
        codes = jnp.minimum(codes, limit)  # CLAMP -> unbounded error
        bw = jnp.full((codes.shape[0],), self.rate_bits, jnp.int32)
        cap = capacity_words_for(n, self.rate_bits / 32.0 + 1e-9, self.block)
        packed, nwords = bitpack.pack(codes, bw, cap)
        return Compressed(
            packed=packed, bitwidth=bw, anchor=anchor, nwords=nwords, eb=eb,
            n=n, block=self.block,
        )

    def stream_nwords(self, bitwidth: jnp.ndarray, n: int) -> jnp.ndarray:
        del n
        return bitpack.packed_words(bitwidth, self.block)

    @jax.named_scope(DECOMPRESS)
    def decompress(self, c: Compressed) -> jnp.ndarray:
        codes = bitpack.unpack(c.packed, c.bitwidth, c.block)
        x2d = ops.dequantize(codes, c.anchor, c.eb)
        return ops.from_blocks(x2d, c.n)

    @jax.named_scope(HOP)
    def decompress_reduce(self, c: Compressed, acc: jnp.ndarray) -> jnp.ndarray:
        return acc + self.decompress(c)

    @jax.named_scope(HOP)
    def decompress_reduce_compress(
        self, c: Compressed, acc: jnp.ndarray, eb_out=None, *,
        return_updated: bool = False,
    ):
        """Composition fallback (fixed-rate has no fused hop kernel)."""
        eb_out = c.eb if eb_out is None else jnp.asarray(eb_out, jnp.float32)
        updated = self.decompress_reduce(c, acc)
        return self.compress(updated, eb_out), (
            updated if return_updated else None
        )


def lossless_capacity_words(n: int, block: int = ops.BLOCK) -> int:
    """Worst-case entropy-stream words for ``n`` elements: every real
    block at its ceiling of ``2 * SUBS * 32 = block`` words (tile-padding
    blocks are all-zero and pack to 0 words).  The structural provisioning
    of the ``lossless`` codec — overflow is impossible by construction."""
    return max(-(-n // block) * block, 8)


@dataclasses.dataclass(frozen=True)
class EntropyLorenzo:
    """Lorenzo quantizer + per-sub-block entropy-coded wire (DESIGN.md §10).

    Quantization is IDENTICAL to ``ErrorBoundedLorenzo`` (the entropy
    stage acts after it, on the zigzag codes), so the error bound is
    untouched; only the wire format changes — each 256-block packs its
    four 64-element sub-blocks at their own widths, descriptor in the
    container's ``bitwidth`` slot.  The stream is never longer than the
    dense bitpack of the same codes, so the dense capacity provisioning
    carries over unchanged.

    ``lossless=True`` swaps the quantizer for a bit-exact
    ``bitcast(f32)->int32`` front end (eb ignored, decompress reproduces
    the input bit-for-bit) — the "lossless" registry entry.  Its capacity
    is STRUCTURAL, not factor-based: each block's four sub-streams total
    at most ``2 * 4 * 32 = BLOCK`` words, so provisioning every real
    block at BLOCK words (``lossless_capacity_words``) can never
    overflow, even on incompressible IEEE bit patterns.

    There is no fused single-pass hop kernel for this format yet, so
    ``decompress_reduce_compress`` is the two-kernel composition (the plan
    layer downgrades ``fused_hop`` with a recorded reason).
    """

    capacity_factor: float = 0.5
    block: int = ops.BLOCK
    fused: bool = True
    lossless: bool = False

    @jax.named_scope(COMPRESS)
    def compress(self, x: jnp.ndarray, eb) -> Compressed:
        n = int(x.size)
        eb = jnp.asarray(eb, jnp.float32)
        x2d = ops.to_blocks(x)
        if self.lossless:
            cap = lossless_capacity_words(n, self.block)
        else:
            cap = capacity_words_for(n, self.capacity_factor, self.block)
        if self.fused:
            packed, desc, anchor = ops.entropy_quantize_pack(
                x2d, eb, cap, lossless=self.lossless
            )
            nwords = entropy.packed_words(desc)
        else:
            codes, anchor = entropy.encode_blocks(x2d, eb, lossless=self.lossless)
            packed, desc, nwords = entropy.pack(codes, cap)
        return Compressed(
            packed=packed, bitwidth=desc, anchor=anchor, nwords=nwords, eb=eb,
            n=n, block=self.block,
        )

    def stream_nwords(self, bitwidth: jnp.ndarray, n: int) -> jnp.ndarray:
        del n
        return entropy.packed_words(bitwidth)

    @jax.named_scope(DECOMPRESS)
    def decompress(self, c: Compressed) -> jnp.ndarray:
        if self.fused:
            x2d = ops.entropy_unpack_dequantize(
                c.packed, c.bitwidth, c.anchor, c.eb, lossless=self.lossless
            )
        else:
            codes = entropy.unpack(c.packed, c.bitwidth, c.block)
            x2d = entropy.decode_blocks(
                codes, c.anchor, c.eb, lossless=self.lossless
            )
        return ops.from_blocks(x2d, c.n)

    @jax.named_scope(HOP)
    def decompress_reduce(self, c: Compressed, acc: jnp.ndarray) -> jnp.ndarray:
        acc2d = ops.to_blocks(acc)
        if self.fused:
            out2d = ops.entropy_unpack_dequantize_reduce(
                c.packed, c.bitwidth, c.anchor, c.eb, acc2d,
                lossless=self.lossless,
            )
        else:
            codes = entropy.unpack(c.packed, c.bitwidth, c.block)
            out2d = acc2d + entropy.decode_blocks(
                codes, c.anchor, c.eb, lossless=self.lossless
            )
        return ops.from_blocks(out2d, c.n)

    @jax.named_scope(HOP)
    def decompress_reduce_compress(
        self, c: Compressed, acc: jnp.ndarray, eb_out=None, *,
        return_updated: bool = False,
    ):
        """Composition hop (no fused entropy hop kernel yet)."""
        assert int(acc.size) == c.n, (acc.size, c.n)
        eb_out = c.eb if eb_out is None else jnp.asarray(eb_out, jnp.float32)
        updated = self.decompress_reduce(c, acc)
        return self.compress(updated, eb_out), (
            updated if return_updated else None
        )


@dataclasses.dataclass(frozen=True)
class Passthrough:
    """Identity codec: raw f32 bit patterns in the ``Compressed`` container.

    The baseline end of the codec registry — wire bytes equal the payload
    (plus container metadata), compression cost is a bitcast copy.  Useful
    when the planner decides compression cannot pay (tiny messages) and as
    the control in codec benchmarks.
    """

    block: int = ops.BLOCK

    @jax.named_scope(COMPRESS)
    def compress(self, x: jnp.ndarray, eb) -> Compressed:
        n = int(x.size)
        eb = jnp.asarray(eb, jnp.float32)
        flat = x.reshape(-1).astype(jnp.float32)
        cap = max(n, 8)
        words = jax.lax.bitcast_convert_type(flat, jnp.int32).astype(jnp.uint32)
        packed = jnp.zeros((cap,), jnp.uint32).at[:n].set(words)
        nb = ops.n_blocks_for(n)
        return Compressed(
            packed=packed,
            bitwidth=jnp.full((nb,), 32, jnp.int32),
            anchor=jnp.zeros((nb,), jnp.int32),
            nwords=jnp.int32(n), eb=eb, n=n, block=self.block,
        )

    def stream_nwords(self, bitwidth: jnp.ndarray, n: int) -> jnp.ndarray:
        del bitwidth
        return jnp.int32(n)

    @jax.named_scope(DECOMPRESS)
    def decompress(self, c: Compressed) -> jnp.ndarray:
        return jax.lax.bitcast_convert_type(
            c.packed[: c.n].astype(jnp.int32), jnp.float32
        )

    @jax.named_scope(HOP)
    def decompress_reduce(self, c: Compressed, acc: jnp.ndarray) -> jnp.ndarray:
        return acc + self.decompress(c)

    @jax.named_scope(HOP)
    def decompress_reduce_compress(
        self, c: Compressed, acc: jnp.ndarray, eb_out=None, *,
        return_updated: bool = False,
    ):
        eb_out = c.eb if eb_out is None else jnp.asarray(eb_out, jnp.float32)
        updated = self.decompress_reduce(c, acc)
        return self.compress(updated, eb_out), (
            updated if return_updated else None
        )


def __getattr__(name: str):
    # PR 8 satellite: the mutable module-global DEFAULT let two configs
    # with different codecs alias one compressor.  Kept as an import-time
    # shim only; resolve instances from the plan's codec entry instead.
    if name == "DEFAULT":
        warnings.warn(
            "compressor.DEFAULT is deprecated: resolve the compressor from "
            "the plan's codec entry via repro.core.codecs.build_compressor "
            "(or GZConfig.compressor()).",
            DeprecationWarning, stacklevel=2,
        )
        return ErrorBoundedLorenzo()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
