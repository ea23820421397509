"""gZCCL compressed collectives as shard_map-level JAX primitives.

Every collective here is written *rank-centric*: it is per-device code that
runs inside a ``jax.shard_map`` body over a named mesh axis, moving
``Compressed`` pytrees with ``jax.lax.ppermute``.  This is the TPU-native
translation of the paper's MPI send/recv patterns (DESIGN.md §2).

Layering (DESIGN.md §5): this module holds the EXECUTE layer — the
``_execute_*`` functions run a fully-resolved schedule (concrete
algorithm, concrete pipeline depth) and contain zero selector logic.
Plan resolution (algorithm choice, pipeline depth, per-stage budgets,
wire accounting) lives in :mod:`repro.core.comm` behind
``GZCommunicator.plan`` and is memoized outside the traced region.  The
public ``gz_*`` functions below are thin back-compat wrappers over a
one-shot communicator; new code should hold a ``GZCommunicator`` and use
its methods, which return the uniform ``CollectiveResult`` stats channel
instead of the legacy ``return_info`` tuple convention.

Algorithms:

  gz_allreduce  algo="redoub"   recursive doubling — log2(N) full-message
                                 compressions (paper's headline gZ-Allreduce)
                algo="ring"      ring reduce-scatter + ring allgather —
                                 (N-1)+1 chunk compressions (paper's
                                 gZ-Allreduce (Ring))
                algo="intring"   BEYOND-PAPER: quantize once, ring-allreduce
                                 the integer codes losslessly — single lossy
                                 hop, bitwise rank-consistent, error <= eb
                                 per addend
                algo="auto"      cost-model selection (core/selector.py)
  gz_reduce_scatter / gz_allgather   the two ring stages standalone
  gz_scatter    binomial tree, per-chunk compression (paper's gZ-Scatter;
                the batched quantize over all chunks is the multi-stream
                analog — one pallas_call covers what N CUDA streams did)
  gz_broadcast  binomial tree, compress once at root

Axis sizes are ARBITRARY (paper §3.2.3, DESIGN.md §7).  The ring schedules
generalize to any N directly; the log-depth schedules handle
non-power-of-two axes with the paper's remainder stage: recursive doubling
folds the n - 2**floor(log2 n) extra ranks into a partner in a compressed
pre-hop, runs the doubling over the remaining power-of-two participants,
and unfolds the result in a compressed post-hop; the binomial
scatter/broadcast trees run ceil(log2 n) rounds on the trimmed-slab
schedule (cost_model.binomial_slab_table): each exchange ships only the
real ranks of the receiver's subtree, so the scatter root wires exactly
n-1 chunk streams at any axis size and out-of-range exchanges never
exist.  The remainder hops are lossy and are charged to the per-stage
error budget (core/error_budget.py: redoub's worst-case hop count is n-1
on power-of-two axes and n otherwise).

Consistency note (recorded in DESIGN.md): like the paper's gZ-Allreduce,
"redoub" and "ring" produce rank-wise results that agree only within the
accumulated error bound (each rank adds *its partner's* requantized data).
"intring" is exact-sum-of-quantized, hence bitwise identical on every rank
— that property is why it exists.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import bitpack, codecs, cost_model, error_budget, faults, \
    schedule
from repro.core.compressed import (
    Compressed, capacity_words_for, validate_capacity_factor,
)
from repro.kernels import ops
from repro.kernels.ref import bitwidth_of as _ref_bitwidth

__all__ = [
    "GZConfig",
    "gz_allreduce",
    "gz_allreduce_hier",
    "gz_reduce_scatter",
    "gz_allgather",
    "gz_scatter",
    "gz_broadcast",
    "gz_all_to_all",
    "plan_ring_pipeline_chunks",
]


@dataclasses.dataclass(frozen=True)
class GZConfig:
    """Knobs for the compressed-collective layer.

    eb is the *end-to-end* absolute error bound; per-stage budgets are
    derived via core.error_budget (accuracy-aware design, paper §3.3.3).

    ``pipeline_chunks`` (power of two) splits every ring chunk into that
    many pieces and software-pipelines the ring: piece k+1 is compressed
    while piece k is in flight on ``ppermute`` — the shard_map analog of
    the paper's multi-stream overlap (§3.2/§3.3, DESIGN.md §4).  1 means
    the sequential schedule; ``algo="auto"`` also auto-selects the chunk
    count from the cost model.  Piece boundaries stay aligned to whole
    compressor row-tiles, so the quantization grid — and therefore the
    error bound and the per-element lossy-hop count — is identical to the
    unpipelined schedule.

    ``fused`` routes compression through the single-pass Pallas pipeline
    (kernels/lorenzo.py quantize_pack); False keeps the two-pass oracle
    composition.  Wire bytes are identical either way.

    ``fused_hop`` runs every intermediate ring/redoub reduce hop as ONE
    ``unpack_reduce_repack`` kernel (DESIGN.md §3.1): the hop's received
    piece is decompressed, reduced and re-compressed into the *next* hop's
    wire stream in a single pass, so the updated f32 chunk never
    round-trips HBM and each hop pays one kernel dispatch instead of two.
    False keeps the PR 1 two-kernel hop schedule (decompress_reduce then a
    separate compress).  Wire streams and results are bitwise identical
    either way; only the kernel count and the cost model's pipeline-depth
    planning differ (``t_hop_fused`` sees one ``cmp_overhead_us``, so
    "auto" picks deeper pipelines when the fused hop is on).

    ``on_overflow`` is the degradation policy (DESIGN.md §9): "flag"
    only reports the global-OR flags in ``CollectiveResult`` (today's
    behaviour); "fallback" re-executes the collective through the
    uncompressed lossless schedule inside the trace (``lax.cond``) when
    any stream overflowed or any input held NaN/Inf, so the result is
    exact whenever compression failed; "raise" raises from an
    ``io_callback`` on the host (debugging aid — aborts the computation).

    ``verify_streams`` ships a per-hop XOR checksum alongside every
    compressed ppermute and treats a mismatch exactly like overflow
    (the stream is unusable either way) — detects in-flight wire
    corruption at the cost of one extra scalar ppermute per hop.

    ``codec`` names a wire-codec registry entry (``repro.core.codecs``,
    DESIGN.md §10): how payload bytes become wire bytes.  "lorenzo" (the
    default) is the dense bitpack — bitwise-unchanged pre-registry
    behavior; "lorenzo+entropy" adds the per-sub-block entropy trim;
    "lossless" / "passthrough" are the eb-free endpoints.  "auto" defers
    the choice to the plan layer, which prices every auto-selectable
    codec through the cost model (calibrated per-codec terms when
    available) and freezes the winner into ``Plan.codec``.
    """

    eb: float = 1e-4
    capacity_factor: float = 0.6
    algo: str = "auto"  # auto | redoub | ring | intring
    worst_case_budget: bool = True
    pipeline_chunks: int = 1
    fused: bool = True
    fused_hop: bool = True
    on_overflow: str = "flag"  # flag | fallback | raise
    verify_streams: bool = False
    codec: str = "lorenzo"  # registry entry name, or "auto"

    def __post_init__(self):
        # Fail at construction time with an actionable message, not via a
        # bare assert buried in an execute-layer tree loop (which would
        # also vanish under `python -O`).
        if self.pipeline_chunks < 1 or not _is_pow2(self.pipeline_chunks):
            raise ValueError(
                "GZConfig.pipeline_chunks must be a power of two >= 1 "
                "(the chunked double-buffered schedules split ring chunks "
                f"and tree slabs in half repeatedly); got "
                f"{self.pipeline_chunks!r}"
            )
        validate_capacity_factor(
            self.capacity_factor, knob="GZConfig.capacity_factor"
        )
        if self.on_overflow not in ("flag", "fallback", "raise"):
            raise ValueError(
                "GZConfig.on_overflow must be one of 'flag' (report only), "
                "'fallback' (in-trace lossless re-execute) or 'raise' "
                f"(host-side error); got {self.on_overflow!r}"
            )
        codecs.validate_codec(self.codec, knob="GZConfig.codec")

    def compressor(self):
        """The wire compressor this config's codec entry resolves to.

        ``codec="auto"`` has no compressor — the plan layer must freeze a
        concrete codec first (``Plan.as_config()`` always does).
        """
        return codecs.build_compressor(
            self.codec, capacity_factor=self.capacity_factor, fused=self.fused
        )


def _axis_size(axis_name) -> int:
    # Composite (tuple/list) axis names — collectives over a flattened 2D
    # mesh ("node", "local") — multiply out.
    if isinstance(axis_name, (tuple, list)):
        n = 1
        for ax in axis_name:
            n *= _axis_size(ax)
        return n
    return lax.axis_size(axis_name)


def _ppermute(tree, axis_name, perm):
    return jax.tree.map(lambda a: lax.ppermute(a, axis_name, perm), tree)


def _ring_perm(n: int):
    """Ring perm, sourced from the schedule authority (core/schedule.py)."""
    return schedule.ring_perm(n)


def _or_across(ovf, axis_name):
    """OR a per-rank overflow flag across the axis (one scalar psum).

    Every collective's per-rank result embeds wire streams compressed on
    OTHER ranks (ring hops, tree forwards, the scatter/broadcast root), so
    a local flag alone can be silently False on a rank whose received data
    was truncated elsewhere.  ``return_info=True`` therefore reports the
    global OR: "did any piece of any hop anywhere overflow".
    """
    return lax.psum(ovf.astype(jnp.int32), axis_name) > 0


def _axis_rank(axis_name):
    """Flattened rank over a (possibly composite) axis, major-to-minor —
    matches the rank order ppermute sees over a tuple axis name."""
    if isinstance(axis_name, (tuple, list)):
        r = jnp.zeros((), jnp.int32)
        for ax in axis_name:
            r = r * _axis_size(ax) + lax.axis_index(ax)
        return r
    return lax.axis_index(axis_name)


def _flags_across(ovf, nonfinite, axis_name):
    """Global-OR both health bits in ONE psum (stacked int32 pair), so the
    psum count per collective is unchanged vs the old single-flag
    ``_or_across``.  Both results are replicated (psum-derived), hence
    safe as ``lax.cond`` predicates."""
    pair = jnp.stack(
        [ovf.astype(jnp.int32), nonfinite.astype(jnp.int32)]
    )
    both = lax.psum(pair, axis_name) > 0
    return both[0], both[1]


def _nonfinite_local(x) -> jnp.ndarray:
    """Per-rank NaN/Inf presence (False scalar for non-float payloads)."""
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.zeros((), jnp.bool_)
    return jnp.any(~jnp.isfinite(x))


def _sanitize(x):
    """Replace NaN/Inf with 0 (identity on finite data, so an
    overflow-only fallback stays bitwise equal to the plain lossless
    collective of the original input)."""
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    return jnp.where(jnp.isfinite(x), x, jnp.zeros((), x.dtype))


def _tree_checksum(tree) -> jnp.ndarray:
    """XOR-fold every leaf's bits into one uint32.

    All wire leaves are 32-bit (packed uint32, bitwidth/anchor/nwords
    int32, eb f32), so a same-width bitcast view is exact; any other
    width falls back to a value cast (still a valid checksum).  A single
    bit flip anywhere in the payload flips exactly one checksum bit.
    """
    total = jnp.zeros((), jnp.uint32)
    for leaf in jax.tree.leaves(tree):
        if leaf.dtype.itemsize == 4:
            words = lax.bitcast_convert_type(leaf, jnp.uint32)
        else:
            words = leaf.astype(jnp.uint32)
        total = total ^ lax.reduce(
            words.reshape(-1), jnp.uint32(0), lax.bitwise_xor, (0,)
        )
    return total


def _ppermute_guarded(tree, axis_name, perm, guard, round_idx=None):
    """``_ppermute`` + optional end-to-end stream verification.

    The fault-injection wire hook (core/faults.py) applies to the
    received payload unconditionally (identity when no fault is
    installed).  ``round_idx`` is the schedule-table round this exchange
    implements (may be a traced loop index) — a round-targeted
    ``FaultSpec(rounds=...)`` corrupts only matching rounds, so an
    injected bitflip lands on the identical wire exchange in the table
    replay and on a real mesh.  With ``guard`` a whole-buffer XOR
    checksum of the SENT tree travels on the same perm as a separate
    scalar ppermute and is compared against a recomputed checksum of the
    received tree; ranks unaddressed by ``perm`` receive zero streams
    AND a zero checksum, so they can never false-positive.  Returns
    ``(recv, bad)``.
    """
    recv = _ppermute(tree, axis_name, perm)
    recv = faults.maybe_corrupt_wire(recv, axis_name, round_idx=round_idx)
    if not guard:
        return recv, jnp.zeros((), jnp.bool_)
    chk_sent = lax.ppermute(_tree_checksum(tree), axis_name, perm)
    return recv, chk_sent != _tree_checksum(recv)


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


# ---------------------------------------------------------------------------
# Lossless fallback schedules (DESIGN.md §9)
# ---------------------------------------------------------------------------
#
# Every op has an uncompressed twin over the same axis/topology.  The
# fallback sanitizes NaN/Inf to 0 first (identity on finite data), so an
# overflow-only degradation recovers the EXACT lossless result and a
# poisoned input recovers the lossless result of the sanitized input.
# The reduction ops lean on XLA's native collectives; scatter/broadcast
# re-walk the SAME trimmed-slab schedule tables with raw f32 payloads
# (the fault-injection wire hook skips non-uint32 trees, so a lossless
# re-execute is immune to the packed-word bit-flip injector).


def _lossless_scatter(x_full, axis_name, cfg: GZConfig, n):
    r = lax.axis_index(axis_name)
    chunk_n = x_full.shape[0] // n
    n_virt = 1 << cost_model.steps_for("binomial", n)
    chunks = _sanitize(x_full.astype(jnp.float32)).reshape(n, chunk_n)
    held = jnp.zeros((n_virt, chunk_n), jnp.float32).at[:n].set(chunks)
    held, _ = _scatter_tree_trimmed(held, axis_name, r, n, n_virt, cfg)
    return jnp.take(held, r, axis=0).astype(x_full.dtype)


def _lossless_broadcast(x, axis_name, cfg: GZConfig, n):
    r = lax.axis_index(axis_name)
    buf = _sanitize(x.reshape(-1).astype(jnp.float32))
    for span, _full, _trim, perm in schedule.tree_plan(n):
        recv = lax.ppermute(buf, axis_name, perm)
        has = (r % (span * 2)) == span
        buf = jnp.where(has, recv, buf)
    return buf.reshape(x.shape).astype(x.dtype)


def _execute_lossless(op, x, axis_name, cfg: GZConfig, *, root: int = 0):
    """Uncompressed re-execute of ``op`` over the same axis (exact)."""
    n = _axis_size(axis_name)
    single = axis_name if not isinstance(axis_name, (tuple, list)) \
        else (axis_name if len(axis_name) > 1 else axis_name[0])
    if op == "allreduce":
        return lax.psum(
            _sanitize(x.astype(jnp.float32)), axis_name
        ).astype(x.dtype)
    if op == "reduce_scatter":
        out = lax.psum_scatter(
            _sanitize(x.astype(jnp.float32)), single,
            scatter_dimension=0, tiled=True,
        )
        return out.astype(x.dtype)
    if op == "allgather":
        v = _sanitize(x)
        if x.ndim == 0:
            return lax.all_gather(v[None], single, tiled=True)
        return lax.all_gather(v, single, tiled=True)
    if op == "scatter":
        return _lossless_scatter(x, axis_name, cfg, n)
    if op == "broadcast":
        return _lossless_broadcast(x, axis_name, cfg, n)
    if op == "all_to_all":
        return lax.all_to_all(
            _sanitize(x), single, split_axis=0, concat_axis=0, tiled=True
        )
    raise ValueError(f"no lossless fallback for op {op!r}")


# ---------------------------------------------------------------------------
# Allreduce — collective computation (paper §3.3.3 / Fig. 4)
# ---------------------------------------------------------------------------


def _redoub_layout(n: int):
    """Remainder-stage layout for recursive doubling over ``n`` ranks
    (paper §3.2.3, DESIGN.md §7).

    ``p = 2**floor(log2 n)`` ranks participate in the XOR doubling; the
    ``rem = n - p`` surplus ranks pair up with a neighbour in a pre-hop:
    each even physical rank ``2i < 2*rem`` folds its data into ``2i + 1``
    and sits out, and gets the result back in a post-hop.  ``phys`` maps a
    virtual participant rank to its physical rank (the odd halves of the
    folded pairs first, then the untouched tail).  Delegates to the
    schedule authority (the same layout the route-table builder uses).
    """
    return schedule.redoub_layout(n)


def _allreduce_redoub(x, axis_name, cfg: GZConfig):
    """Recursive-doubling gZ-Allreduce: ~log2(N) full-message compressions.

    Per step: compress local running sum, exchange with the XOR partner,
    fused decompress+reduce into the local sum.  Full-message compression
    keeps the compressor saturated — the paper's core scalability insight.

    Non-power-of-two axes run the paper's remainder stage around the
    doubling (``_redoub_layout``): a compressed pre-hop folds each surplus
    rank into its partner, the doubling runs over the power-of-two
    participants (idle ranks ride along SPMD-style: their ``ppermute``
    slots are unaddressed, so they receive zero streams that decompress to
    0.0 and leave their accumulator untouched), and a compressed post-hop
    unfolds the result.  Both remainder hops are ordinary lossy exchanges
    charged to the stage budget (``error_budget.lossy_hops`` counts n
    instead of n-1), and overflow flags are masked to streams that
    actually travel so an idle rank's dead compression can never trip the
    global OR.

    With ``cfg.fused_hop`` every intermediate step runs as a single
    ``decompress_reduce_compress`` pass: the received partner stream and
    the local sum go in, the *next* step's outgoing stream comes out
    (plus the updated f32 carry, which redoub genuinely needs); the last
    step emits the plain f32 accumulator — except on a remainder axis,
    where the last step's fused kernel directly emits the post-hop's
    outgoing stream alongside the carry (the unfold payload IS the
    compressed updated accumulator).  ceil(log2 N)+1 kernels instead of
    2·ceil(log2 N) (+1 on remainder axes), bitwise-identical results.
    """
    n = _axis_size(axis_name)
    comp = cfg.compressor()
    eb_stage = error_budget.allocate(
        cfg.eb, "allreduce_redoub", n, worst_case=cfg.worst_case_budget
    )
    p, rem, _phys = _redoub_layout(n)
    steps = p.bit_length() - 1  # == log2(p)
    r = lax.axis_index(axis_name)
    # Remainder-stage masks (all False / trivially true when rem == 0).
    in_pair = r < 2 * rem
    is_fold_src = in_pair & (r % 2 == 0)   # folds into partner, then idles
    is_fold_dst = in_pair & (r % 2 == 1)   # absorbs partner, sends back
    is_participant = ~is_fold_src
    # Every perm comes from the route table: round 0 is the fold pre-hop
    # (remainder axes only), rounds base..base+steps-1 the XOR doubling,
    # round base+steps the unfold post-hop.
    sched = schedule.build("allreduce", "redoub", n)
    base = 1 if rem else 0
    pre_perm = sched.perm(0) if rem else ()
    step_perms = [sched.perm(base + k) for k in range(steps)]
    post_perm = sched.perm(base + steps) if rem else ()
    acc = x
    overflow = jnp.zeros((), jnp.bool_)

    guard = cfg.verify_streams

    if cfg.fused_hop:
        c = comp.compress(acc, eb_stage)
        # The initial stream travels on the pre-hop (fold sources) on a
        # remainder axis, on step 0 (everyone) otherwise.
        overflow |= c.overflowed() & (is_fold_src if rem else True)
        if rem:
            c_recv, bad = _ppermute_guarded(
                c, axis_name, pre_perm, guard, round_idx=0
            )
            overflow |= bad
            c, acc = comp.decompress_reduce_compress(
                c_recv, acc, eb_stage, return_updated=True
            )
            overflow |= c.overflowed() & is_participant
        for k in range(steps):
            c_recv, bad = _ppermute_guarded(
                c, axis_name, step_perms[k], guard, round_idx=base + k
            )
            overflow |= bad
            if k < steps - 1:
                c, acc = comp.decompress_reduce_compress(
                    c_recv, acc, eb_stage, return_updated=True
                )
                overflow |= c.overflowed() & is_participant
            elif rem:
                # Last hop + post-stage compress in one fused pass: the
                # unfold payload is the stream of the updated accumulator.
                c, acc = comp.decompress_reduce_compress(
                    c_recv, acc, eb_stage, return_updated=True
                )
                overflow |= c.overflowed() & is_fold_dst
            else:  # last hop: emit the plain f32 accumulator
                acc = comp.decompress_reduce(c_recv, acc)
        if rem:
            c_back, bad = _ppermute_guarded(
                c, axis_name, post_perm, guard, round_idx=base + steps
            )
            overflow |= bad
            acc = jnp.where(is_fold_src, comp.decompress(c_back), acc)
        return acc, overflow

    if rem:
        c = comp.compress(acc, eb_stage)
        overflow |= c.overflowed() & is_fold_src
        c_recv, bad = _ppermute_guarded(
            c, axis_name, pre_perm, guard, round_idx=0
        )
        overflow |= bad
        acc = comp.decompress_reduce(c_recv, acc)
    for k in range(steps):
        c = comp.compress(acc, eb_stage)
        overflow |= c.overflowed() & is_participant
        c_recv, bad = _ppermute_guarded(
            c, axis_name, step_perms[k], guard, round_idx=base + k
        )
        overflow |= bad
        acc = comp.decompress_reduce(c_recv, acc)
    if rem:
        c = comp.compress(acc, eb_stage)
        overflow |= c.overflowed() & is_fold_dst
        c_back, bad = _ppermute_guarded(
            c, axis_name, post_perm, guard, round_idx=base + steps
        )
        overflow |= bad
        acc = jnp.where(is_fold_src, comp.decompress(c_back), acc)
    return acc, overflow


def _chunk(x, idx, chunk_n):
    return lax.dynamic_slice(x, (idx * chunk_n,), (chunk_n,))


def _set_chunk(x, val, idx, chunk_n):
    return lax.dynamic_update_slice(x, val, (idx * chunk_n,))


def _pad_to_chunks(x, n):
    total = -(-x.shape[0] // n) * n
    return jnp.zeros((total,), x.dtype).at[: x.shape[0]].set(x), total // n


def _reduce_scatter_ring(x, axis_name, cfg: GZConfig, eb_stage, *, owner_offset=0):
    """Ring reduce-scatter with per-hop compression of the running chunk sum.

    Returns (acc, chunk_n, overflow): rank r's fully-reduced chunk is at
    index (r + 1 + owner_offset) % N of its local acc.  (N-1) compressions
    of size D/N each — the regime where the paper shows compressor
    under-utilization.

    Single-pass hop schedule (``cfg.fused_hop``): the chunk a hop reduces
    into IS the chunk the next hop sends, so each intermediate hop runs ONE
    ``decompress_reduce_compress`` kernel that turns the received stream +
    the local chunk directly into the next outgoing stream — the updated
    f32 never lands in ``acc`` (nothing ever reads it back; callers only
    read the final chunk).  The LAST hop emits the plain f32 accumulator.
    N kernels total instead of 2(N-1), byte-identical wire streams.
    """
    n = _axis_size(axis_name)
    comp = cfg.compressor()
    r = lax.axis_index(axis_name)
    acc, chunk_n = _pad_to_chunks(x, n)
    perm = _ring_perm(n)
    overflow = jnp.zeros((), jnp.bool_)
    t = owner_offset

    guard = cfg.verify_streams

    if cfg.fused_hop:
        c = comp.compress(_chunk(acc, (r + t) % n, chunk_n), eb_stage)
        overflow |= c.overflowed()

        def body(s, carry):
            c, overflow = carry
            c_recv, bad = _ppermute_guarded(c, axis_name, perm, guard,
                                            round_idx=s)
            recv_idx = (r - s - 1 + t) % n
            c_next, _ = comp.decompress_reduce_compress(
                c_recv, _chunk(acc, recv_idx, chunk_n), eb_stage
            )
            return c_next, overflow | bad | c_next.overflowed()

        c, overflow = lax.fori_loop(0, n - 2, body, (c, overflow))
        c_recv, bad = _ppermute_guarded(c, axis_name, perm, guard,
                                        round_idx=n - 2)
        overflow |= bad
        recv_idx = (r - (n - 2) - 1 + t) % n
        updated = comp.decompress_reduce(c_recv, _chunk(acc, recv_idx, chunk_n))
        return _set_chunk(acc, updated, recv_idx, chunk_n), chunk_n, overflow

    def body(s, carry):
        acc, overflow = carry
        send_idx = (r - s + t) % n
        recv_idx = (r - s - 1 + t) % n
        c = comp.compress(_chunk(acc, send_idx, chunk_n), eb_stage)
        overflow |= c.overflowed()
        c_recv, bad = _ppermute_guarded(c, axis_name, perm, guard,
                                        round_idx=s)
        overflow |= bad
        updated = comp.decompress_reduce(c_recv, _chunk(acc, recv_idx, chunk_n))
        return _set_chunk(acc, updated, recv_idx, chunk_n), overflow

    acc, overflow = lax.fori_loop(0, n - 1, body, (acc, overflow))
    return acc, chunk_n, overflow


# ---------------------------------------------------------------------------
# Chunked double-buffered (pipelined) ring schedule — DESIGN.md §4
# ---------------------------------------------------------------------------
#
# Each ring chunk is split into P = cfg.pipeline_chunks pieces, each a whole
# number of compressor row-tiles so the quantization grid matches the
# sequential schedule exactly.  The (step, piece) loop is flattened to
# t = s*P + p and software-pipelined with one piece of double buffering:
# the body at iteration t ppermutes the *already compressed* piece t while
# compressing piece t+1 from the pre-update accumulator.  For P >= 2 the
# piece compressed at t is never the piece reduced at t (next step's piece
# 0 was received P-1 iterations earlier), so the compress has no data
# dependency on the in-flight ppermute — XLA's scheduler is free to overlap
# them, which is the shard_map translation of the paper's multi-stream
# compress/communicate overlap.

PIECE_QUANTUM = ops.BLOCK * ops.TILE_ROWS  # elements per compressor row-tile


def _piece(x, chunk_idx, piece_idx, chunk_n, piece_n):
    return lax.dynamic_slice(
        x, (chunk_idx * chunk_n + piece_idx * piece_n,), (piece_n,)
    )


def _set_piece(x, val, chunk_idx, piece_idx, chunk_n, piece_n):
    return lax.dynamic_update_slice(
        x, val, (chunk_idx * chunk_n + piece_idx * piece_n,)
    )


def _pad_for_pipeline(x, n, p):
    """Pad flat x so each of n chunks is p pieces of whole row-tiles."""
    quantum = n * p * PIECE_QUANTUM
    total = -(-x.shape[0] // quantum) * quantum
    padded = jnp.zeros((total,), x.dtype).at[: x.shape[0]].set(x)
    return padded, total // n, total // (n * p)


def plan_ring_pipeline_chunks(n_elems: int, n_ranks: int, *, ratio: float = 20.0,
                              hw=None, fused_hop: bool = True) -> int:
    """Cost-model pipeline depth for a ring over `n_elems` f32 elements,
    capped at what the payload can actually fill with whole-tile pieces.

    The one planner every entry point (gz_allreduce auto, grad_sync
    routing) shares, so identical messages get identical schedules.
    ``fused_hop`` must match the schedule the collective will actually run
    (GZConfig.fused_hop): the single-pass hop halves the per-piece kernel
    overhead, so its optimum is deeper.
    """
    chunks = cost_model.best_pipeline_chunks(
        n_elems * 4, n_ranks, ratio,
        hw if hw is not None else cost_model.TPU_V5E, fused_hop=fused_hop,
    )
    fill = n_elems // (n_ranks * PIECE_QUANTUM)
    while chunks > 1 and chunks > fill:
        chunks //= 2
    return chunks


def _stack_trees(trees):
    """Stack a list of identical pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _index_tree(tree, i):
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree
    )


def _update_tree(tree, val, i):
    return jax.tree.map(
        lambda a, v: lax.dynamic_update_index_in_dim(a, v, i, 0), tree, val
    )


def _reduce_scatter_ring_pipelined(x, axis_name, cfg: GZConfig, eb_stage, *,
                                   owner_offset=0):
    """Chunked double-buffered ring reduce-scatter.

    Same hop structure and error budget as :func:`_reduce_scatter_ring`
    (every element is still requantized once per hop); only the schedule
    changes: compress(piece t+1) runs concurrently with ppermute(piece t).
    Returns (acc, chunk_n, overflow) with the same ownership convention.

    With ``cfg.fused_hop`` the schedule keeps the same overlap shape but
    each intermediate hop is ONE kernel: the fused hop that consumed piece
    p at step s already produced the stream piece p sends at step s+1, so
    the body only issues the next piece's ppermute (independent — its
    stream was produced P-1 hops ago) alongside the current hop's fused
    kernel.  The pending streams ride the loop carry as a stacked
    ``Compressed`` (leading axis = piece); the last step's P hops drain to
    the plain f32 accumulator.
    """
    n = _axis_size(axis_name)
    p_chunks = cfg.pipeline_chunks
    assert p_chunks >= 2, "pipelined schedule needs >= 2 pieces per chunk"
    comp = cfg.compressor()
    r = lax.axis_index(axis_name)
    acc, chunk_n, piece_n = _pad_for_pipeline(x, n, p_chunks)
    perm = _ring_perm(n)
    t0 = owner_offset
    T = (n - 1) * p_chunks

    guard = cfg.verify_streams

    if cfg.fused_hop:
        # Pipeline fill: step 0's send chunk, compressed as P pieces.
        send0 = (r + t0) % n
        overflow = jnp.zeros((), jnp.bool_)
        pend = []
        for p in range(p_chunks):
            c = comp.compress(_piece(acc, send0, p, chunk_n, piece_n), eb_stage)
            overflow |= c.overflowed()
            pend.append(c)
        pend = _stack_trees(pend)
        c_fly, bad0 = _ppermute_guarded(
            _index_tree(pend, 0), axis_name, perm, guard, round_idx=0
        )
        overflow |= bad0

        def body(u, carry):
            pend, c_fly, overflow = carry
            # Wire the NEXT hop's stream while this hop's fused kernel
            # runs: pend[(u+1) % P] was produced by hop u+1-P (or the
            # fill), so the ppermute has no dependency on this hop.
            c_fly_next, bad = _ppermute_guarded(
                _index_tree(pend, (u + 1) % p_chunks), axis_name, perm,
                guard, round_idx=(u + 1) // p_chunks,
            )
            s, p = u // p_chunks, u % p_chunks
            recv_idx = (r - s - 1 + t0) % n
            c_next, _ = comp.decompress_reduce_compress(
                c_fly, _piece(acc, recv_idx, p, chunk_n, piece_n), eb_stage
            )
            pend = _update_tree(pend, c_next, p)
            return pend, c_fly_next, overflow | bad | c_next.overflowed()

        # Fused hops cover steps 0..n-3; the last step drains below.
        pend, c_fly, overflow = lax.fori_loop(
            0, T - p_chunks, body, (pend, c_fly, overflow)
        )
        recv_last = (r - (n - 2) - 1 + t0) % n
        for p in range(p_chunks):
            if p + 1 < p_chunks:
                c_fly_next, bad = _ppermute_guarded(
                    _index_tree(pend, p + 1), axis_name, perm, guard,
                    round_idx=n - 2,
                )
                overflow |= bad
            updated = comp.decompress_reduce(
                c_fly, _piece(acc, recv_last, p, chunk_n, piece_n)
            )
            acc = _set_piece(acc, updated, recv_last, p, chunk_n, piece_n)
            if p + 1 < p_chunks:
                c_fly = c_fly_next
        return acc, chunk_n, overflow

    def send_piece(acc, t):
        s, p = t // p_chunks, t % p_chunks
        send_idx = (r - s + t0) % n
        return comp.compress(
            _piece(acc, send_idx, p, chunk_n, piece_n), eb_stage
        )

    c0 = send_piece(acc, 0)  # pipeline fill: piece 0 compressed up front
    overflow = c0.overflowed()

    def body(t, carry):
        acc, c_in, overflow = carry
        # Compress the NEXT piece from the pre-update accumulator: for
        # P >= 2 that piece was last touched at least P-1 iterations ago,
        # so this op is independent of the ppermute below (the overlap).
        c_next = send_piece(acc, t + 1)
        overflow |= c_next.overflowed()
        c_recv, bad = _ppermute_guarded(c_in, axis_name, perm, guard,
                                        round_idx=t // p_chunks)
        overflow |= bad
        s, p = t // p_chunks, t % p_chunks
        recv_idx = (r - s - 1 + t0) % n
        updated = comp.decompress_reduce(
            c_recv, _piece(acc, recv_idx, p, chunk_n, piece_n)
        )
        acc = _set_piece(acc, updated, recv_idx, p, chunk_n, piece_n)
        return acc, c_next, overflow

    acc, c_last, overflow = lax.fori_loop(0, T - 1, body, (acc, c0, overflow))
    # Pipeline drain: the final piece's hop.
    c_recv, bad = _ppermute_guarded(c_last, axis_name, perm, guard,
                                    round_idx=n - 2)
    overflow |= bad
    recv_idx = (r - (n - 2) - 1 + t0) % n
    updated = comp.decompress_reduce(
        c_recv, _piece(acc, recv_idx, p_chunks - 1, chunk_n, piece_n)
    )
    acc = _set_piece(acc, updated, recv_idx, p_chunks - 1, chunk_n, piece_n)
    return acc, chunk_n, overflow


def _compress_own_pieces(buf, own_idx, eb, cfg: GZConfig, chunk_n, piece_n,
                         overflow):
    """Compress chunk `own_idx` of `buf` as P independent pieces, installing
    the decompressed copy in place (owner sees the same values everyone
    else will).  Returns (buf, pieces tuple, overflow)."""
    comp = cfg.compressor()
    pieces = []
    for p in range(cfg.pipeline_chunks):
        c = comp.compress(_piece(buf, own_idx, p, chunk_n, piece_n), eb)
        overflow |= c.overflowed()
        buf = _set_piece(buf, comp.decompress(c), own_idx, p, chunk_n, piece_n)
        pieces.append(c)
    return buf, tuple(pieces), overflow


def _forward_pieces_ring(buf, pieces, axis_name, cfg: GZConfig, recv_idx_fn,
                         chunk_n, piece_n, round_offset=0):
    """Forward P compressed pieces around the ring for n-1 steps, installing
    decompressed copies at chunk ``recv_idx_fn(s)`` each step.

    Each piece rides its own ppermute chain, so decompress(piece p) can
    overlap the wire time of piece p+1 at every step — the chunked
    double-buffered allgather schedule.  Exactly one lossy hop per element
    (the compression happened once, at the owner).
    """
    n = _axis_size(axis_name)
    comp = cfg.compressor()
    perm = _ring_perm(n)
    guard = cfg.verify_streams

    def body(s, carry):
        buf, pieces, bad = carry
        recv_idx = recv_idx_fn(s)
        new_pieces = []
        for p, c_p in enumerate(pieces):
            c_new, b = _ppermute_guarded(c_p, axis_name, perm, guard,
                                         round_idx=round_offset + s)
            bad |= b
            buf = _set_piece(
                buf, comp.decompress(c_new), recv_idx, p, chunk_n, piece_n
            )
            new_pieces.append(c_new)
        return buf, tuple(new_pieces), bad

    buf, _, bad = lax.fori_loop(
        0, n - 1, body, (buf, pieces, jnp.zeros((), jnp.bool_))
    )
    return buf, bad


def _allgather_forward_pipelined(acc, axis_name, cfg: GZConfig, eb_stage,
                                 chunk_n, piece_n, overflow):
    """Pipelined ring-allgather forwarding stage over an RS-reduced acc."""
    n = _axis_size(axis_name)
    r = lax.axis_index(axis_name)
    acc, pieces, overflow = _compress_own_pieces(
        acc, (r + 1) % n, eb_stage, cfg, chunk_n, piece_n, overflow
    )
    acc, bad = _forward_pieces_ring(
        acc, pieces, axis_name, cfg,
        lambda s: (r - s) % n,  # chunk owned by rank (r - 1 - s)
        chunk_n, piece_n,
        round_offset=n - 1,  # allgather rounds follow the n-1 RS rounds
    )
    return acc, overflow | bad


def _allreduce_ring(x, axis_name, cfg: GZConfig):
    """Ring gZ-Allreduce: reduce-scatter stage + allgather-forwarding stage.

    The allgather stage compresses exactly once (owner) and forwards the
    *compressed* payload N-1 times (no recompression — the paper's
    data-movement framework), so it adds exactly one lossy hop.  With
    ``cfg.pipeline_chunks > 1`` both stages run the chunked
    double-buffered schedule (same lossy-hop count, overlapped pipeline).
    """
    n = _axis_size(axis_name)
    comp = cfg.compressor()
    hops = error_budget.lossy_hops("allreduce_ring", n)
    eb_stage = cfg.eb / hops if cfg.worst_case_budget else cfg.eb / math.sqrt(hops)
    r = lax.axis_index(axis_name)

    if cfg.pipeline_chunks > 1:
        acc, chunk_n, overflow = _reduce_scatter_ring_pipelined(
            x, axis_name, cfg, eb_stage
        )
        acc, overflow = _allgather_forward_pipelined(
            acc, axis_name, cfg, eb_stage, chunk_n,
            chunk_n // cfg.pipeline_chunks, overflow,
        )
        return acc[: x.shape[0]], overflow

    acc, chunk_n, overflow = _reduce_scatter_ring(x, axis_name, cfg, eb_stage)
    own_idx = (r + 1) % n

    # Allgather stage: compress own reduced chunk once; every rank (owner
    # included) uses the decompressed version so all ranks see the same
    # values for this chunk.
    c_own = comp.compress(_chunk(acc, own_idx, chunk_n), eb_stage)
    overflow |= c_own.overflowed()
    acc = _set_chunk(acc, comp.decompress(c_own), own_idx, chunk_n)
    perm = _ring_perm(n)
    guard = cfg.verify_streams

    def body(s, carry):
        acc, c_cur, bad = carry
        c_new, b = _ppermute_guarded(c_cur, axis_name, perm, guard,
                                     round_idx=(n - 1) + s)
        recv_idx = (r - s) % n  # chunk owned by rank (r - 1 - s)
        acc_new = _set_chunk(acc, comp.decompress(c_new), recv_idx, chunk_n)
        return acc_new, c_new, bad | b

    acc, _, bad = lax.fori_loop(
        0, n - 1, body, (acc, c_own, jnp.zeros((), jnp.bool_))
    )
    return acc[: x.shape[0]], overflow | bad


def _allreduce_intring(x, axis_name, cfg: GZConfig):
    """BEYOND-PAPER integer-domain ring allreduce.

    Quantize once (the only lossy step), then ring-reduce-scatter +
    ring-allgather the *integer Lorenzo-delta codes* with lossless
    repacking.  Lorenzo deltas are linear (delta(a+b) = delta(a)+delta(b))
    and anchors add, so summation happens entirely in the delta domain and
    reconstruction (anchor + cumsum) is done once at the end.  Properties
    the paper's algorithms lack:

      * bitwise-identical result on every rank (int sums are exact), and
      * a single quantization grid — error vs the true sum is the sum of N
        independent initial quantization errors (<= N*eb worst case,
        ~sqrt(N)*eb statistically) with NO stacked requantization noise.

    Wire width grows by at most log2(step) bits per block over the ring.
    """
    n = _axis_size(axis_name)
    r = lax.axis_index(axis_name)
    eb = jnp.float32(cfg.eb)
    n_orig = x.shape[0]
    B = ops.BLOCK
    # Pad so each of the n chunks is a whole number of kernel row-tiles.
    rows_per_chunk = ops.n_blocks_for(-(-n_orig // n))
    chunk_n = rows_per_chunk * B
    xf = jnp.zeros((n * chunk_n,), jnp.float32).at[:n_orig].set(x)
    # One lossy step: quantize everything (batched over all chunks).
    zig, _, anchor = ops.quantize(xf.reshape(-1, B), eb)
    d = (zig >> 1).astype(jnp.int32) ^ (-(zig & 1).astype(jnp.int32))
    state = (d, anchor)  # delta codes (nrows, B) + anchors (nrows,)

    cap = capacity_words_for(chunk_n, cfg.capacity_factor, B)
    perm = _ring_perm(n)

    def getc(t, idx):
        d, a = t
        return (
            lax.dynamic_slice(d, (idx * rows_per_chunk, 0), (rows_per_chunk, B)),
            lax.dynamic_slice(a, (idx * rows_per_chunk,), (rows_per_chunk,)),
        )

    def setc(t, val, idx):
        d, a = t
        dv, av = val
        return (
            lax.dynamic_update_slice(d, dv, (idx * rows_per_chunk, 0)),
            lax.dynamic_update_slice(a, av, (idx * rows_per_chunk,)),
        )

    def addc(a, b):
        return (a[0] + b[0], a[1] + b[1])

    def pack_codes(dc):
        dd, aa = dc
        z = ((dd << 1) ^ (dd >> 31)).astype(jnp.uint32)
        bw = _ref_bitwidth(jnp.max(z, axis=1))
        packed, nwords = bitpack.pack(z, bw, cap)
        return (packed, bw, aa), nwords

    def unpack_codes(w):
        packed, bw, aa = w
        u = bitpack.unpack(packed, bw, B)
        return ((u >> 1).astype(jnp.int32) ^ (-(u & 1).astype(jnp.int32)), aa)

    overflow = jnp.zeros((), jnp.bool_)
    guard = cfg.verify_streams

    def rs_body(s, carry):
        state, overflow = carry
        send_idx = (r - s) % n
        recv_idx = (r - s - 1) % n
        wire, nwords = pack_codes(getc(state, send_idx))
        overflow |= nwords > cap
        wire, bad = _ppermute_guarded(wire, axis_name, perm, guard,
                                      round_idx=s)
        state = setc(state, addc(getc(state, recv_idx), unpack_codes(wire)), recv_idx)
        return state, overflow | bad

    state, overflow = lax.fori_loop(0, n - 1, rs_body, (state, overflow))
    own_idx = (r + 1) % n
    wire, nwords = pack_codes(getc(state, own_idx))
    overflow |= nwords > cap

    def ag_body(s, carry):
        state, cur, bad = carry
        nxt, b = _ppermute_guarded(cur, axis_name, perm, guard,
                                   round_idx=(n - 1) + s)
        recv_idx = (r - s) % n
        state = setc(state, unpack_codes(nxt), recv_idx)
        return state, nxt, bad | b

    state, _, bad = lax.fori_loop(
        0, n - 1, ag_body, (state, wire, jnp.zeros((), jnp.bool_))
    )
    overflow |= bad
    d, anchor = state
    q = anchor[:, None] + jnp.cumsum(d, axis=1)
    out = (q.astype(jnp.float32) * (2.0 * eb)).reshape(-1)
    return out[:n_orig], overflow


def _execute_allreduce(x, axis_name, cfg: GZConfig):
    """EXECUTE layer: run a fully-resolved allreduce schedule.

    ``cfg.algo`` must be concrete — ``"auto"`` is a plan-time concern and
    lives in core/comm.py (``GZCommunicator.plan``); nothing in here may
    consult the selector or the cost model.  Returns
    ``(out, local_overflow)``; the caller owns the cross-axis OR.
    """
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    if cfg.algo == "redoub":
        out, ovf = _allreduce_redoub(flat, axis_name, cfg)
    elif cfg.algo == "ring":
        out, ovf = _allreduce_ring(flat, axis_name, cfg)
    elif cfg.algo == "intring":
        out, ovf = _allreduce_intring(flat, axis_name, cfg)
    else:
        raise ValueError(
            f"unresolved allreduce algo {cfg.algo!r} reached the execute "
            "layer — resolve a Plan via GZCommunicator.plan first"
        )
    return out.reshape(shape).astype(dtype), ovf


def _execute_allreduce_hier(x, node_axis, local_axis, hplan):
    """EXECUTE layer for the two-level (node × intra-node) allreduce.

    ``hplan`` is a fully-resolved ``comm.HierPlan``.  The flat branch runs
    the ordinary single-axis schedule over the COMPOSITE axis
    ``(node_axis, *local)`` — ppermute/psum accept tuple axis names, with
    ranks flattened node-major — so "hierarchy off" is literally the
    pre-existing code path, not a reimplementation (the bitwise-equality
    guarantee the degenerate-topology property test relies on).

    The hierarchical branch composes three stages (DESIGN.md §8):

      1. UNCOMPRESSED ``lax.psum_scatter`` over the local axis — exact
         f32 sums on the fast intra-node link; each local rank ends up
         with one fully node-reduced shard of ceil(D/L) elements.
      2. The compressed single-axis allreduce of that shard across the
         node axis (``hplan.inter`` — the ONLY lossy stage, carrying the
         whole error budget via ``error_budget.split_lossy``).
      3. UNCOMPRESSED ``lax.all_gather`` over the local axis to
         rematerialize the full message.

    ``local_axis`` may itself be a tuple of mesh axes (grad-sync collapses
    every non-node data-parallel axis into "local").
    """
    local = tuple(local_axis) if isinstance(local_axis, (tuple, list)) \
        else (local_axis,)
    if hplan.flat:
        return _execute_allreduce(
            x, (node_axis,) + local, hplan.flat_plan.as_config()
        )
    n_nodes, L = hplan.topology
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    padded, _shard_n = _pad_to_chunks(flat, L)
    if L > 1:
        shard = lax.psum_scatter(
            padded, local if len(local) > 1 else local[0],
            scatter_dimension=0, tiled=True,
        )
    else:
        shard = padded
    ovf = jnp.zeros((), jnp.bool_)
    if n_nodes > 1:
        shard, ovf = _execute_allreduce(
            shard, node_axis, hplan.inter.as_config()
        )
    if L > 1:
        padded = lax.all_gather(
            shard, local if len(local) > 1 else local[0], tiled=True
        )
    else:
        padded = shard
    return padded[: flat.shape[0]].reshape(shape).astype(dtype), ovf


def gz_allreduce_hier(
    x: jnp.ndarray,
    node_axis,
    local_axis,
    cfg: GZConfig = GZConfig(),
    *,
    return_info: bool = False,
):
    """Two-level topology-aware allreduce (back-compat-style wrapper over
    a one-shot :class:`~repro.core.comm.GZHierCommunicator`).  New code
    should hold the communicator and use its ``allreduce`` method."""
    from repro.core.comm import GZHierCommunicator

    res = GZHierCommunicator.for_axes(node_axis, local_axis, config=cfg) \
        .allreduce(x)
    return (res.value, res.overflow) if return_info else res.value


def _comm_for(axis_name, cfg: GZConfig):
    from repro.core.comm import GZCommunicator

    return GZCommunicator.for_config(axis_name, cfg)


def gz_allreduce(
    x: jnp.ndarray,
    axis_name,
    cfg: GZConfig = GZConfig(),
    *,
    return_info: bool = False,
):
    """Compression-accelerated allreduce (sum) over a mesh axis.

    Call inside a shard_map body.  ``x`` may have any shape/float dtype;
    compression runs on the f32 flat view and the result is cast back.

    Back-compat wrapper over a one-shot :class:`~repro.core.comm.
    GZCommunicator` (bitwise-identical to ``comm.allreduce(x).value``);
    ``return_info=True`` unpacks the ``CollectiveResult`` into the legacy
    ``(value, overflow)`` tuple.  New code should hold a communicator.
    """
    res = _comm_for(axis_name, cfg).allreduce(x)
    return (res.value, res.overflow) if return_info else res.value


# ---------------------------------------------------------------------------
# Reduce_scatter / Allgather — the ring stages standalone
# ---------------------------------------------------------------------------


def _execute_reduce_scatter(x, axis_name, cfg: GZConfig):
    """EXECUTE layer for the ring reduce-scatter (concrete schedule)."""
    n = _axis_size(axis_name)
    if x.ndim != 1 or x.shape[0] % n != 0:
        raise ValueError(
            f"gz_reduce_scatter over axis {axis_name!r} (size {n}): the "
            "payload must be flat with length divisible by the axis size "
            f"(rank r returns summed chunk r); got shape {tuple(x.shape)}"
        )
    eb_stage = error_budget.allocate(
        cfg.eb, "reduce_scatter_ring", n, worst_case=cfg.worst_case_budget
    )
    r = lax.axis_index(axis_name)
    flat = x.astype(jnp.float32)
    chunk_in = x.shape[0] // n
    if cfg.pipeline_chunks > 1:
        # Chunk boundaries are caller semantics: pad each chunk (not the
        # flat tail) so every chunk is pipeline_chunks whole-tile pieces.
        quantum = cfg.pipeline_chunks * PIECE_QUANTUM
        chunk_pad = -(-chunk_in // quantum) * quantum
        flat = (
            jnp.zeros((n, chunk_pad), jnp.float32)
            .at[:, :chunk_in]
            .set(flat.reshape(n, chunk_in))
            .reshape(-1)
        )
        acc, chunk_n, ovf = _reduce_scatter_ring_pipelined(
            flat, axis_name, cfg, eb_stage, owner_offset=-1
        )
    else:
        # owner_offset=-1 makes rank r end owning chunk r (see derivation in
        # _reduce_scatter_ring docstring).
        acc, chunk_n, ovf = _reduce_scatter_ring(
            flat, axis_name, cfg, eb_stage, owner_offset=-1
        )
    return _chunk(acc, r % n, chunk_n)[:chunk_in].astype(x.dtype), ovf


def gz_reduce_scatter(
    x: jnp.ndarray, axis_name, cfg: GZConfig = GZConfig(), *, return_info: bool = False
):
    """Ring reduce-scatter: rank r returns the summed chunk r (flat view).

    x: (n*chunk,) per rank (same on-wire layout as lax.psum_scatter with
    tiled=True over a flat array).  Back-compat wrapper over the one-shot
    communicator — ``comm.reduce_scatter`` returns the full
    ``CollectiveResult``.
    """
    res = _comm_for(axis_name, cfg).reduce_scatter(x)
    return (res.value, res.overflow) if return_info else res.value


def _execute_allgather(x, axis_name, cfg: GZConfig):
    """EXECUTE layer for the ring allgather (concrete schedule)."""
    n = _axis_size(axis_name)
    comp = cfg.compressor()
    r = lax.axis_index(axis_name)
    dtype = x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    n_orig = flat.shape[0]

    if cfg.pipeline_chunks > 1:
        quantum = cfg.pipeline_chunks * PIECE_QUANTUM
        chunk_n = -(-n_orig // quantum) * quantum
        piece_n = chunk_n // cfg.pipeline_chunks
        own_chunk = jnp.zeros((chunk_n,), jnp.float32).at[:n_orig].set(flat)
        padded = lax.dynamic_update_slice(
            jnp.zeros((n * chunk_n,), jnp.float32), own_chunk, (r * chunk_n,)
        )
        out, pieces, ovf = _compress_own_pieces(
            padded, r, cfg.eb, cfg, chunk_n, piece_n, jnp.zeros((), jnp.bool_)
        )
        out, bad = _forward_pieces_ring(
            out, pieces, axis_name, cfg,
            lambda s: (r - s - 1) % n,  # piece sent by rank (r - 1 - s)
            chunk_n, piece_n,
        )
        ovf |= bad
        out = out.reshape(n, chunk_n)[:, :n_orig].reshape(-1)
        out = out.reshape((n * x.shape[0],) + x.shape[1:]) if x.ndim else out
        return out.astype(dtype), ovf

    chunk_n = n_orig
    out = jnp.zeros((n * chunk_n,), jnp.float32)
    c_own = comp.compress(flat, cfg.eb)
    ovf = c_own.overflowed()
    out = _set_chunk(out, comp.decompress(c_own), r, chunk_n)
    perm = _ring_perm(n)
    guard = cfg.verify_streams

    def body(s, carry):
        out, c_cur, bad = carry
        c_new, b = _ppermute_guarded(c_cur, axis_name, perm, guard,
                                     round_idx=s)
        src = (r - s - 1) % n
        out = _set_chunk(out, comp.decompress(c_new), src, chunk_n)
        return out, c_new, bad | b

    out, _, bad = lax.fori_loop(
        0, n - 1, body, (out, c_own, jnp.zeros((), jnp.bool_))
    )
    out = out.reshape((n * x.shape[0],) + x.shape[1:]) if x.ndim else out
    return out.astype(dtype), ovf | bad


def gz_allgather(
    x: jnp.ndarray, axis_name, cfg: GZConfig = GZConfig(), *, return_info: bool = False
):
    """Ring allgather: compress once, forward compressed N-1 times.

    x: (chunk,) per rank -> returns (n*chunk,) with rank j's data at slot j.
    Exactly one lossy hop end-to-end (data-movement framework): the returned
    slot j holds decompress(compress(x_j)) on *every* rank including j.
    Back-compat wrapper over the one-shot communicator.
    """
    res = _comm_for(axis_name, cfg).allgather(x)
    return (res.value, res.overflow) if return_info else res.value


# ---------------------------------------------------------------------------
# Scatter / Broadcast — collective data movement (paper §3.3.4 / Fig. 5)
# ---------------------------------------------------------------------------


def _wire_container(comp, packed, bitwidth, anchor, eb, n) -> Compressed:
    """Rebuild a ``Compressed`` from bare wire parts on the receive side
    (the batched scatter/all-to-all paths ship the leaves, not the pytree);
    the true stream size is recomputed from the codec's own metadata."""
    return Compressed(
        packed=packed, bitwidth=bitwidth, anchor=anchor,
        nwords=comp.stream_nwords(bitwidth, n),
        eb=jnp.asarray(eb, jnp.float32), n=n, block=ops.BLOCK,
    )


def _scatter_held_buffers(x_full, n, cfg: GZConfig):
    """Batched per-chunk compression into the tree's held buffers.

    Each chunk is padded to whole row-tiles so chunk boundaries align with
    block boundaries, then ONE quantize call covers all chunks (the
    multi-stream analog: what N CUDA streams did in the paper, one grid
    does here).  Held buffers live in a virtual ``2**ceil(log2 n)`` rank
    space (zero streams in the padding slots) so slab indexing is uniform;
    under the trimmed schedule the padding slots never travel and are never
    read — they exist only to keep the ``dynamic_slice`` extents static.
    Returns ``(held (packed, bw, anchor), rows, chunk_n, n_virt, ovf)``.
    """
    chunk_n = x_full.shape[0] // n
    rows = ops.n_blocks_for(chunk_n)
    B = ops.BLOCK
    chunks = x_full.astype(jnp.float32).reshape(n, chunk_n)
    n_virt = 1 << cost_model.steps_for("binomial", n)
    if cfg.codec != "lorenzo":
        # Non-default codecs go through the compressor interface per chunk
        # (their pack kernels are not batched across chunk boundaries);
        # the held-buffer layout (packed, bitwidth, anchor) is identical.
        comp = cfg.compressor()
        ovf = jnp.zeros((), jnp.bool_)
        cs = []
        for i in range(n):
            c = comp.compress(chunks[i], cfg.eb)
            cs.append(c)
            ovf |= c.overflowed()
        packed0 = jnp.stack([c.packed for c in cs])
        bw = jnp.stack([c.bitwidth for c in cs])
        anchor = jnp.stack([c.anchor for c in cs])
    else:
        x2d = (
            jnp.zeros((n, rows * B), jnp.float32).at[:, :chunk_n].set(chunks)
        ).reshape(n * rows, B)
        codes, bw, anchor = ops.quantize(x2d, cfg.eb)
        cap = capacity_words_for(chunk_n, cfg.capacity_factor, B)
        ovf = jnp.zeros((), jnp.bool_)
        pk_list = []
        for i in range(n):
            pk, nw = bitpack.pack(
                codes[i * rows : (i + 1) * rows],
                bw[i * rows : (i + 1) * rows], cap
            )
            pk_list.append(pk)
            ovf |= nw > cap
        packed0 = jnp.stack(pk_list)  # (n, cap)
        bw = bw.reshape(n, rows)
        anchor = anchor.reshape(n, rows)
    held = (
        jnp.zeros((n_virt,) + packed0.shape[1:], packed0.dtype).at[:n].set(
            packed0),
        jnp.zeros((n_virt, rows), bw.dtype).at[:n].set(bw),
        jnp.zeros((n_virt, rows), anchor.dtype).at[:n].set(anchor),
    )
    return held, rows, chunk_n, n_virt, ovf


def _slab_exchange(held, axis_name, r, perm, start, slab, n_virt, is_recv,
                   guard=False, round_idx=None):
    """Ship a ``slab``-chunk window of the held buffers along ``perm`` and
    install it at the receiver's own rank index (everyone else keeps its
    buffer).  One static ppermute shape per call.  Returns
    ``(held, bad)`` — ``bad`` is the receive-side stream-verification
    flag (always False when ``guard`` is off), masked to actual
    receivers."""
    piece = jax.tree.map(
        lambda h: lax.dynamic_slice(
            h, (start % n_virt,) + (0,) * (h.ndim - 1),
            (slab,) + h.shape[1:],
        ),
        held,
    )
    recv, bad = _ppermute_guarded(piece, axis_name, perm, guard,
                                  round_idx=round_idx)
    installed = jax.tree.map(
        lambda h, rv: lax.dynamic_update_slice(
            h, rv, (r,) + (0,) * (h.ndim - 1)
        ),
        held,
        recv,
    )
    held = jax.tree.map(
        lambda new, old: jnp.where(is_recv, new, old), installed, held
    )
    return held, bad & is_recv


def _scatter_tree_trimmed(held, axis_name, r, n, n_virt, cfg: GZConfig):
    """Trimmed-slab binomial tree (DESIGN.md §7): each round ships only
    the real ranks of the receiver's subtree.

    The schedule comes from ``schedule.tree_plan`` — the route table the
    plan layer prices and the simulator replays, with each round's
    ``ppermute`` perm taken verbatim from the table's hop entries.  Per
    round: the full-span exchanges (receiver subtree entirely real) run
    as today, split into ``cfg.pipeline_chunks`` piece-permute chains;
    the at most one boundary exchange ships its ``n - receiver`` real
    chunks as ONE extra ppermute shape (its slab size is not a power of
    two, so it is not piece-split).  The padding slots of the held
    buffers never travel: the root ships exactly n-1 chunk streams at
    any axis size.
    """
    guard = cfg.verify_streams
    corrupt = jnp.zeros((), jnp.bool_)
    for k, (span, full_senders, trim, perm) in enumerate(
        schedule.tree_plan(n)
    ):
        start = r + span  # sender's outgoing slab start (own subtree's right half)
        # The table lists the full-span entries first, then the at most
        # one trimmed boundary entry — slice, don't re-derive.
        perm_full = perm[: len(full_senders)]
        if full_senders:
            # Full receivers: the span-aligned odd subtree heads whose
            # whole virtual subtree is real.
            is_recv = ((r % (span * 2)) == span) & (r + span <= n)
            groups = min(max(cfg.pipeline_chunks, 1), span)
            sub = span // groups
            for g in range(groups):
                held, bad = _slab_exchange(
                    held, axis_name, r + g * sub, perm_full,
                    start + g * sub, sub, n_virt, is_recv, guard,
                    round_idx=k,
                )
                corrupt |= bad
        if trim is not None:
            snd, rcv, slab = trim
            held, bad = _slab_exchange(
                held, axis_name, r, perm[len(full_senders):], start, slab,
                n_virt, r == rcv, guard, round_idx=k,
            )
            corrupt |= bad
    return held, corrupt


def _scatter_tree_padded_reference(held, axis_name, r, n, n_virt,
                                   cfg: GZConfig):
    """The PR 4 padded virtual-tree walk, kept verbatim as the byte-parity
    ORACLE for the trimmed schedule (tests only — every real rank must
    decode identical bytes from both walks; see the multi-device children).
    Round k ships a full 2**k-chunk slab — padding chunks included — from
    each sender ``i % 2**(k+1) == 0`` to ``i + 2**k``.
    """
    steps = n_virt.bit_length() - 1
    corrupt = jnp.zeros((), jnp.bool_)
    for k in reversed(range(steps)):
        span = 1 << k
        # schedule-authority: allow — PR 4 byte-parity oracle, kept verbatim
        perm = [(i, i + span) for i in range(0, n_virt, span * 2)
                if i + span < n]
        is_recv = (r % (span * 2)) == span
        groups = min(max(cfg.pipeline_chunks, 1), span)
        sub = span // groups
        for g in range(groups):
            held, bad = _slab_exchange(
                held, axis_name, r + g * sub, perm, r + span + g * sub,
                sub, n_virt, is_recv, cfg.verify_streams,
            )
            corrupt |= bad
    return held, corrupt


def _execute_scatter(x_full, axis_name, cfg: GZConfig, *, root: int = 0,
                     _padded_reference: bool = False):
    """EXECUTE layer for the binomial-tree scatter (concrete schedule).

    Arbitrary axis sizes run the TRIMMED-SLAB schedule (DESIGN.md §7):
    ``ceil(log2 n)`` rounds over a virtual power-of-two rank space, but
    each exchange ships only the real ranks of the receiver's subtree
    (``schedule.tree_plan``), so the root's provisioned wire
    is exactly n-1 chunk streams at any n — the virtual tree's padding
    chunks are held locally (zero streams keeping slab arithmetic static)
    and never travel.  On power-of-two axes the schedule is identical to
    the classic binomial tree.  ``_padded_reference=True`` runs the PR 4
    padded walk instead (test oracle; same bytes at every real rank).
    """
    n = _axis_size(axis_name)
    if root != 0:
        raise ValueError(
            f"gz_scatter over axis {axis_name!r} (size {n}): only root 0 "
            f"is supported (the binomial tree is rooted at rank 0); got "
            f"root={root}.  Roll the payload so the source rank is 0."
        )
    if x_full.shape[0] % n != 0:
        raise ValueError(
            f"gz_scatter over axis {axis_name!r} (size {n}): the full "
            "payload's leading dim must be divisible by the axis size "
            f"(each rank receives one chunk); got shape "
            f"{tuple(x_full.shape)}"
        )
    r = lax.axis_index(axis_name)
    dtype = x_full.dtype
    held, rows, chunk_n, n_virt, ovf = _scatter_held_buffers(x_full, n, cfg)
    tree = (_scatter_tree_padded_reference if _padded_reference
            else _scatter_tree_trimmed)
    (held_packed, held_bw, held_anchor), corrupt = tree(
        held, axis_name, r, n, n_virt, cfg
    )

    # Only the root compresses significant data; the SPMD packs of the
    # other ranks' local buffers are meaningless and must not pollute the
    # global overflow OR below.  Wire corruption is a receive-side event
    # and is NOT root-masked: a corrupted stream is unusable wherever it
    # lands.
    ovf = (ovf & (r == 0)) | corrupt

    # Decompress own chunk (the single lossy hop).
    my_pk = jnp.take(held_packed, r, axis=0)
    my_bw = jnp.take(held_bw, r, axis=0)
    my_anchor = jnp.take(held_anchor, r, axis=0)
    if cfg.codec != "lorenzo":
        comp = cfg.compressor()
        c = _wire_container(comp, my_pk, my_bw, my_anchor, cfg.eb, chunk_n)
        return comp.decompress(c).astype(dtype), ovf
    if cfg.fused:
        x2d = ops.unpack_dequantize(my_pk, my_bw, my_anchor, cfg.eb)
    else:
        my_codes = bitpack.unpack(my_pk, my_bw, ops.BLOCK)
        x2d = ops.dequantize(my_codes, my_anchor, cfg.eb)
    return ops.from_blocks(x2d, chunk_n).astype(dtype), ovf


def gz_scatter(
    x_full: jnp.ndarray,
    axis_name,
    cfg: GZConfig = GZConfig(),
    *,
    root: int = 0,
    return_info: bool = False,
):
    """Binomial-tree compressed scatter (gZ-Scatter).

    ``x_full``: (n*chunk,) — significant on the root rank only.  Each of the
    N chunks is compressed *individually* (compressed streams are not
    splittable — paper §3.3.4), in ONE batched quantize call: the
    multi-stream analog.  Blocks travel compressed through the tree and are
    decompressed exactly once by their final owner.  Back-compat wrapper
    over the one-shot communicator.
    """
    res = _comm_for(axis_name, cfg).scatter(x_full, root=root)
    return (res.value, res.overflow) if return_info else res.value


def gz_all_to_all(x: jnp.ndarray, axis_name, cfg: GZConfig = GZConfig()):
    """Compressed all-to-all (beyond-paper; motivated by the MoE-dispatch
    ablation in benchmarks/moe_a2a_ablation.py).

    x: (n*chunk, ...) per rank — slot buffers grouped by destination rank
    along the leading dim.  Each destination chunk is compressed
    individually (ONE batched quantize — the multi-stream analog), the
    packed buffers travel through ``lax.all_to_all``, and each rank
    decompresses what it received.  Exactly one lossy hop per element.
    Returns (n*chunk, ...) with the received chunks stacked in rank order.

    Differentiable (straight-through the quantizer): the rank-exchange
    layout is self-inverse, so the transpose is the same compressed
    exchange applied to the cotangent — the custom_vjp lives on the
    plan-dispatched ``comm._a2a_planned``.  Back-compat wrapper over the
    one-shot communicator; ``comm.all_to_all`` also reports overflow/wire
    stats via ``CollectiveResult``.
    """
    return _comm_for(axis_name, cfg).all_to_all(x).value


def _execute_all_to_all(x, axis_name, cfg: GZConfig):
    """EXECUTE layer for the compressed rank exchange (one lossy hop)."""
    n = _axis_size(axis_name)
    if x.shape[0] % n != 0:
        raise ValueError(
            f"gz_all_to_all over axis {axis_name!r} (size {n}): the leading "
            "dim must be divisible by the axis size (slot buffers grouped "
            f"by destination rank); got shape {tuple(x.shape)}"
        )
    shape, dtype = x.shape, x.dtype
    chunk_rows = x.shape[0] // n
    chunk_n = chunk_rows * int(np.prod(shape[1:])) if len(shape) > 1 else chunk_rows
    B = ops.BLOCK
    rows = ops.n_blocks_for(chunk_n)
    flat = x.reshape(n, chunk_n).astype(jnp.float32)
    if cfg.codec != "lorenzo":
        comp = cfg.compressor()
        ovf = jnp.zeros((), jnp.bool_)
        cs = []
        for i in range(n):
            c = comp.compress(flat[i], cfg.eb)
            cs.append(c)
            ovf |= c.overflowed()
        packed = jnp.stack([c.packed for c in cs])
        bw = jnp.stack([c.bitwidth for c in cs])
        anchor = jnp.stack([c.anchor for c in cs])
    else:
        x2d = (
            jnp.zeros((n, rows * B), jnp.float32).at[:, :chunk_n].set(flat)
        ).reshape(n * rows, B)
        codes, bw, anchor = ops.quantize(x2d, cfg.eb)
        cap = capacity_words_for(chunk_n, cfg.capacity_factor, B)
        ovf = jnp.zeros((), jnp.bool_)
        pk = []
        for i in range(n):
            p, nw = bitpack.pack(
                codes[i * rows : (i + 1) * rows],
                bw[i * rows : (i + 1) * rows], cap
            )
            pk.append(p)
            ovf |= nw > cap
        packed = jnp.stack(pk)  # (n, cap)
        bw = bw.reshape(n, rows)
        anchor = anchor.reshape(n, rows)
    # ship: tiled=False removes the leading (== axis size) dim and stacks
    # the received peers' chunks back at position 0
    recv = jax.tree.map(
        lambda a: lax.all_to_all(a, axis_name, split_axis=0, concat_axis=0,
                                 tiled=False),
        (packed, bw, anchor),
    )
    rp, rb, ra = recv
    out = []
    if cfg.codec != "lorenzo":
        comp = cfg.compressor()
        for i in range(n):
            c = _wire_container(comp, rp[i], rb[i], ra[i], cfg.eb, chunk_n)
            out.append(comp.decompress(c))
    else:
        for i in range(n):
            if cfg.fused:
                x2d = ops.unpack_dequantize(rp[i], rb[i], ra[i], cfg.eb)
            else:
                c = bitpack.unpack(rp[i], rb[i], B)
                x2d = ops.dequantize(c, ra[i], cfg.eb)
            out.append(ops.from_blocks(x2d, chunk_n))
    out = jnp.stack(out).reshape(shape).astype(dtype)
    return out, ovf


def _execute_broadcast(x, axis_name, cfg: GZConfig, *, root: int = 0):
    """EXECUTE layer for the binomial-tree broadcast (concrete schedule).

    Arbitrary axis sizes: ``ceil(log2 n)`` rounds of halving spans whose
    forwarding pairs come from the SAME trimmed schedule authority as the
    scatter (``schedule.tree_plan`` — the full-span pairs plus the
    at-most-one trimmed boundary pair per round; exchanges whose
    receiver does not exist never appear).  The payload is the one full
    compressed message either way, so trimming changes no bytes here — it
    guarantees schedule/accounting cannot drift (DESIGN.md §7): every real
    rank's sender chain stays inside the real ranks, coverage and the
    one-lossy-hop property are unchanged.
    """
    n = _axis_size(axis_name)
    if root != 0:
        raise ValueError(
            f"gz_broadcast over axis {axis_name!r} (size {n}): only root 0 "
            f"is supported (the binomial tree is rooted at rank 0); got "
            f"root={root}."
        )
    comp = cfg.compressor()
    r = lax.axis_index(axis_name)
    shape, dtype = x.shape, x.dtype
    c = comp.compress(x.reshape(-1).astype(jnp.float32), cfg.eb)
    # Non-root ranks compress their (insignificant) local x in SPMD; only
    # the root's stream travels, so only its flag is meaningful.
    ovf = c.overflowed() & (r == 0)
    guard = cfg.verify_streams
    for k, (span, _full, _trim, perm) in enumerate(schedule.tree_plan(n)):
        c_recv, bad = _ppermute_guarded(c, axis_name, perm, guard,
                                        round_idx=k)
        has = (r % (span * 2)) == span
        ovf |= bad & has
        c = jax.tree.map(lambda new, old: jnp.where(has, new, old), c_recv, c)
    return comp.decompress(c).reshape(shape).astype(dtype), ovf


def gz_broadcast(
    x: jnp.ndarray,
    axis_name,
    cfg: GZConfig = GZConfig(),
    *,
    root: int = 0,
    return_info: bool = False,
):
    """Binomial-tree compressed broadcast: compress once at root, forward
    the compressed stream down the tree, decompress once per rank.
    Back-compat wrapper over the one-shot communicator."""
    res = _comm_for(axis_name, cfg).broadcast(x, root=root)
    return (res.value, res.overflow) if return_info else res.value
