"""Plan-then-execute communicator surface for the gZ collectives.

The paper's §3 premise is that compression-accelerated collectives are a
*framework*: one place coordinates algorithm choice, overlap depth, and
accuracy-aware per-stage error budgets.  Before this module that
coordination was smeared across call sites — every ``gz_*`` call
re-derived its plan at trace time and callers hand-assembled ``GZConfig``
knob-bags.  ZCCL frames exactly this as a communicator-level concern, and
NCCLZ argues for a plan-then-execute surface rather than per-call knobs;
this module is that surface for the shard_map collectives:

  * :class:`GZCommunicator` binds ONE mesh axis (name + size) and the
    static knobs (eb, capacity, policy, hardware model) once.
  * ``comm.plan(op, shape, dtype)`` resolves a frozen, hashable
    :class:`Plan` — concrete algorithm, pipeline depth, per-stage eb,
    capacity words, provisioned wire bytes — OUTSIDE the traced region,
    memoized module-wide per ``(op, nbytes, dtype, axis_size, eb)`` plus
    the policy knobs.  Repeated jitted calls (and re-traces) hit the
    cache; the cost model runs exactly once per distinct key.
  * The collectives are methods (``allreduce``/``reduce_scatter``/
    ``allgather``/``scatter``/``broadcast``/``all_to_all``) that dispatch
    on the Plan with zero in-trace selector logic, and every one of them
    returns the same :class:`CollectiveResult` stats channel — no more
    ``return_info: bool`` tuple convention.

Static vs traced (DESIGN.md §5): everything in a ``Plan`` is static
Python — algorithm strings, chunk counts, byte counts, floats.  The only
traced values are the payload itself and the ``CollectiveResult.overflow``
flag (a global OR across the axis, one scalar psum).  Plans can therefore
be resolved eagerly outside ``jit``, closed over, or resolved lazily at
trace time — either way the resolution is a dict lookup after the first
call.

Policies (the registry is extensible via :func:`register_policy`):

  ``auto``        cost-model selection under the production (fused-hop,
                  chunked double-buffered) schedules; ring gets its
                  pipeline depth from ``best_pipeline_chunks`` capped by
                  what the payload can fill.  The default, and exactly
                  what ``gz_allreduce(algo="auto")`` always did.
  ``paper``       the paper's §3.3.3 selector: ring vs recursive doubling
                  under the two-kernel multi-stream cost models,
                  sequential schedule — reproduces the published
                  crossover.
  ``throughput``  like ``auto`` but also allowed to pick the
                  beyond-paper integer ring when it models fastest.
  ``accuracy``    the bitwise-rank-consistent integer ring (single
                  quantization grid, no stacked requantization noise)
                  regardless of modeled speed.

Calibration: :func:`fit_hardware` fits ``cost_model.Hardware`` codec
parameters (throughput + per-invocation overhead) from measured
``(size, seconds)`` samples — ``measure_codec`` produces them with the
same timing discipline as the microbenchmark suite — and
``comm.calibrate()`` returns a communicator whose plans use the fitted
model.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import io_callback

from repro.core import codecs, cost_model, error_budget, faults, schedule
from repro.core.compressed import capacity_words_for
from repro.kernels import ops

__all__ = [
    "Plan",
    "HierPlan",
    "FallbackPlan",
    "CollectiveResult",
    "GZCommunicator",
    "GZHierCommunicator",
    "select_allreduce",
    "select_allreduce_plan",
    "assert_step_count_consistency",
    "register_policy",
    "policy_names",
    "plan_cache_stats",
    "clear_plan_cache",
    "enable_health_tracking",
    "health_stats",
    "clear_health_stats",
    "fit_hardware",
    "fit_network",
    "fit_codec_terms",
    "measure_codec",
    "measure_codecs",
    "measure_ppermute",
]

OPS = (
    "allreduce",
    "reduce_scatter",
    "allgather",
    "scatter",
    "broadcast",
    "all_to_all",
)

# Fixed algorithm per data-movement op (only allreduce has a real choice).
_OP_ALGO = {
    "reduce_scatter": "ring",
    "allgather": "ring",
    "scatter": "binomial",
    "broadcast": "binomial",
    "all_to_all": "direct",
}


# ---------------------------------------------------------------------------
# Plan & CollectiveResult
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FallbackPlan:
    """The lossless degradation target of a compressed plan (DESIGN.md §9).

    Every resolved :class:`Plan`/:class:`HierPlan` carries one: the
    uncompressed schedule over the SAME axis/topology that
    ``on_overflow="fallback"`` re-executes through when a stream
    overflowed, a verified hop failed its checksum, or an input held
    NaN/Inf.  Static and hashable like every other plan field.
    """

    op: str
    kind: str          # lossless primitive: psum | psum_scatter | ...
    axis_size: int
    wire_bytes: int    # raw uncompressed bytes the fallback moves per rank
    t_model: float     # modeled seconds of one fallback execution


# Lossless primitive each op degrades to (FallbackPlan.kind).
_FALLBACK_KIND = {
    "allreduce": "psum",
    "reduce_scatter": "psum_scatter",
    "allgather": "all_gather",
    "scatter": "raw_slab_tree",
    "broadcast": "raw_tree_forward",
    "all_to_all": "all_to_all",
}


def _fallback_plan(op, n_elems, axis_size, hw) -> FallbackPlan:
    return FallbackPlan(
        op=op, kind=_FALLBACK_KIND[op], axis_size=axis_size,
        wire_bytes=n_elems * 4,
        t_model=cost_model.fallback_time(op, n_elems * 4, axis_size, hw),
    )


@dataclasses.dataclass(frozen=True)
class Plan:
    """A frozen, hashable execution plan for one collective call.

    Every field is static Python (hashable — the plan is a valid
    ``custom_vjp`` nondiff argument and a valid dict key).  ``eb_stage``,
    ``capacity_words``, ``wire_bytes`` and ``ratio`` are *derived*
    observability fields: execution re-derives the same quantities from
    the same inputs (single source of truth is ``error_budget`` /
    ``capacity_words_for``), so a Plan can never disagree with what runs.
    """

    op: str               # one of OPS
    algo: str             # concrete algorithm — never "auto"
    n_elems: int          # flat f32 element count of the per-rank payload
    nbytes: int           # n_elems * 4 (collectives run on the f32 view)
    dtype: str            # caller dtype (cast back on the way out)
    axis_size: int
    eb: float             # end-to-end absolute error bound
    eb_stage: float       # per-stage bound from error_budget.allocate
    pipeline_chunks: int  # concrete depth (>= 1)
    fused: bool
    fused_hop: bool
    capacity_factor: float
    worst_case_budget: bool
    capacity_words: int   # provisioned uint32 words per wire stream
    wire_bytes: int       # provisioned bytes shipped per rank (upper bound)
    ratio: float          # uncompressed-equivalent bytes / wire_bytes
    policy: str
    # Binomial-tree ops only: derived observability field like eb_stage /
    # wire_bytes above — a frozen copy of the trimmed-slab schedule
    # (cost_model.binomial_slab_table(axis_size): per-round
    # (span, full_senders, (sender, receiver, slab)|None), top-down).
    # The execute layer and simulator re-derive the same table from the
    # same single authority, so this can never disagree with what runs.
    # Static and hashable like every other field; () for non-tree ops.
    slab_table: tuple = ()
    # Degradation policy (DESIGN.md §9): what the communicator does when
    # overflow/NaN/Inf/corruption fires, and whether hops ship checksums.
    on_overflow: str = "flag"   # flag | fallback | raise
    verify_streams: bool = False
    # The resolved lossless degradation target — always present (the
    # fallback schedule exists whether or not the policy executes it).
    fallback: Optional[FallbackPlan] = None
    # Wire codec (DESIGN.md §10): always a CONCRETE registry name, never
    # "auto" — the planner resolves selection before freezing the plan.
    # ``codec_ratio`` is the measured-or-modeled payload ratio the codec
    # was priced at (calibrated ``Hardware.codec_terms`` win over the
    # registry's modeled defaults); ``ratio`` above stays the provisioned
    # wire reduction.  ``notes`` records resolution decisions a caller
    # would otherwise have to re-derive (codec forcing, fused-hop
    # downgrades, auto selection).
    codec: str = "lorenzo"
    codec_ratio: float = 1.0
    notes: tuple = ()
    # The resolved Schedule IR (ISSUE 10): the frozen per-round route
    # table the execute layer walks, the simulator replays, the wire
    # accounting sums and the fault injector targets — authored once by
    # ``schedule.build`` at plan resolution.  None only on plans built
    # by hand in tests.
    route_table: Optional[schedule.Schedule] = None

    def as_config(self):
        """The concrete GZConfig the execute layer dispatches on."""
        from repro.core.collectives import GZConfig

        return GZConfig(
            eb=self.eb,
            capacity_factor=self.capacity_factor,
            algo=self.algo,
            worst_case_budget=self.worst_case_budget,
            pipeline_chunks=self.pipeline_chunks,
            fused=self.fused,
            fused_hop=self.fused_hop,
            on_overflow=self.on_overflow,
            verify_streams=self.verify_streams,
            codec=self.codec,
        )


@dataclasses.dataclass(frozen=True)
class HierPlan:
    """A frozen, hashable plan for one TWO-LEVEL collective call.

    Composes per-axis sub-:class:`Plan`s over a ``(n_nodes, local)``
    topology (the FULL axis-size tuple — 2×4 and 4×2 are different plans
    with different schedules, which is why the cache below keys on the
    tuple, not the product).  ``flat`` picks which sub-plan executes
    (``flat_plan`` is always resolved — the flat alternative is the
    comparison baseline benchmarks record; ``inter`` exists only on the
    hierarchical path):

      * ``flat=True``: run the ordinary single-axis schedule
        (``flat_plan``) over the composite ``(node, *local)`` axis — the
        resolution when the fabric has no link asymmetry (or only one
        rank per node), so "hierarchy off" is bitwise the pre-existing
        path.
      * ``flat=False``: uncompressed intra-node reduce-scatter →
        compressed ``inter`` allreduce of the ceil(D/L) shard across
        nodes (the only lossy stage; it carries the WHOLE error budget —
        ``error_budget.split_lossy`` gives the exact intra stages 0) →
        uncompressed intra-node allgather.

    ``inter_wire_bytes`` is the per-rank payload crossing node
    boundaries: the hierarchical path ships only the inter sub-plan's
    provisioned streams; the flat path's node-major ring makes EVERY send
    of a node-boundary rank cross, so its inter wire is the full
    single-axis ``wire_bytes`` — the quantity ``benchmarks/hier_bench.py``
    records and ``regression_check.py`` pins.  ``t_model``/``t_flat`` are
    the modeled seconds of the chosen path and the flat alternative
    (per-link terms: ``cost_model.allreduce_hier_gz`` vs the flat model).
    """

    op: str
    topology: tuple        # (n_nodes, gpus_per_node) — full axis-size tuple
    n_elems: int
    nbytes: int
    dtype: str
    eb: float
    flat: bool
    inter: Optional[Plan]       # compressed inter-node stage (hier path)
    flat_plan: Optional[Plan]   # composite-axis plan (flat path)
    intra_wire_bytes: int  # uncompressed intra-node bytes per rank (RS+AG)
    inter_wire_bytes: int  # provisioned bytes crossing node boundaries/rank
    t_model: float         # modeled seconds of the chosen path
    t_flat: float          # modeled seconds of the flat alternative
    policy: str
    # Degradation policy + the composite-axis lossless target (§9); the
    # sub-plans carry their own fallback/verify knobs via as_config().
    on_overflow: str = "flag"
    verify_streams: bool = False
    fallback: Optional[FallbackPlan] = None
    # Wire codec of the path that executes (the flat sub-plan's, or the
    # inter stage's on the hierarchical path — the intra stages are
    # uncompressed and carry no codec).
    codec: str = "lorenzo"
    # The resolved Schedule IR of the path that executes: the flat
    # sub-plan's table, or the two-level composition from
    # ``schedule.build_hier`` (raw exact intra rounds around the lifted
    # compressed inter rounds) on the hierarchical path.
    route_table: Optional[schedule.Schedule] = None

    @property
    def ratio(self) -> float:
        """Inter-node wire reduction vs what the flat path would cross."""
        if self.flat:
            return self.flat_plan.ratio
        if not self.inter_wire_bytes:
            return 1.0
        return self.inter.ratio


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CollectiveResult:
    """Uniform result-and-stats channel of every communicator method.

    ``value``/``overflow``/``nonfinite`` are traced; ``wire_bytes``/
    ``ratio`` are static (pytree aux data) so the container flows through
    ``jit``/``shard_map`` like a 3-leaf pytree.

    ``overflow`` is the global OR across the axis ("did any piece of any
    hop anywhere exceed its provisioned capacity, or fail stream
    verification") — the per-rank local flag alone can be silently False
    on a rank whose *received* data was truncated elsewhere.

    ``nonfinite`` is the distinct health bit for NaN/Inf detected in the
    INPUT before compression (a non-finite value entering the quantizer
    poisons the packed stream undetectably, so it is checked up front) —
    global OR across the axis, root-masked for scatter/broadcast where
    only the root's payload is significant.  Under
    ``on_overflow="fallback"`` either bit routes the call through the
    lossless schedule (``overflow | nonfinite`` is the re-execute
    predicate; the ``degraded`` property).

    ``wire_bytes`` is the statically provisioned payload a rank ships for
    the whole collective (XLA moves provisioned capacity, not the ragged
    true stream — DESIGN.md §2.1); ``ratio`` is the uncompressed
    equivalent divided by that, i.e. the wire reduction this plan achieves
    on the static-shape transport.
    """

    value: jnp.ndarray
    overflow: jnp.ndarray
    nonfinite: jnp.ndarray
    wire_bytes: int = dataclasses.field(metadata=dict(static=True))
    ratio: float = dataclasses.field(metadata=dict(static=True))

    @property
    def degraded(self) -> jnp.ndarray:
        """True iff this call could not complete losslessly-bounded
        compressed (the fallback predicate)."""
        return self.overflow | self.nonfinite

    def astuple(self):
        return (self.value, self.overflow, self.nonfinite,
                self.wire_bytes, self.ratio)


# ---------------------------------------------------------------------------
# Provisioned wire accounting (static, from the plan inputs alone)
# ---------------------------------------------------------------------------


def _stream_bytes(n_elems: int, capacity_factor: float,
                  codec: str = "lorenzo") -> int:
    """Wire bytes of one provisioned ``Compressed`` stream for n f32.

    Capacity comes from :func:`codecs.codec_capacity_words` — the same
    provisioning authority the compressor factories use — so per-codec
    overrides (lossless' 1.25 factor, passthrough's structural n words)
    price exactly the buffers the execute layer ships.  The metadata
    sidecar (per-block bitwidth/descriptor + anchor) is the same
    container shape for every codec.
    """
    cap = codecs.codec_capacity_words(codec, n_elems, capacity_factor)
    n_blocks = ops.n_blocks_for(n_elems)
    return cap * 4 + 2 * n_blocks * 4 + 8  # packed + bitwidth + anchor + meta


def _int_stream_bytes(n_elems_padded: int, capacity_factor: float) -> int:
    """intring hop payload: packed codes + per-block bitwidth + anchor.

    ``n_elems_padded`` must already be whole blocks (the execute layer
    pads each chunk to whole row-tiles before quantizing)."""
    cap = capacity_words_for(n_elems_padded, capacity_factor, ops.BLOCK)
    rows = n_elems_padded // ops.BLOCK
    return cap * 4 + 2 * rows * 4


# Elements per compressor row-tile — the pipelined schedules' piece quantum
# (same constant as collectives.PIECE_QUANTUM; duplicated here to keep the
# module import-cycle-free).
_PIECE_QUANTUM = ops.BLOCK * ops.TILE_ROWS


def _ring_piece_sizes(n_elems, n, chunks):
    """(chunk, piece) the ring schedules actually run: pipelined rings pad
    the payload so each of the n chunks is `chunks` whole-tile pieces
    (collectives._pad_for_pipeline)."""
    p = max(chunks, 1)
    if p > 1:
        quantum = n * p * _PIECE_QUANTUM
        total = -(-n_elems // quantum) * quantum
        return total // n, total // (n * p)
    chunk = -(-n_elems // n)
    return chunk, chunk


def _wire_accounting(op, algo, n_elems, n, capacity_factor, chunks,
                     codec: str = "lorenzo"):
    """(capacity_words, wire_bytes, uncompressed_bytes) for one call.

    Per-rank send bytes, upper bound: SUM the resolved route table
    (``schedule.build(op, algo, n)`` — the same table the execute layer
    walks and the simulator replays, ISSUE 10).  Every entry is priced by
    the payload it ships at the op's transport granularity (full message,
    padded ring piece, tree chunk slab, integer code rows — the
    ``_entry_pricers`` closures mirror the execute layer's padding), the
    per-sender totals are accumulated, and the busiest rank's total is
    the provisioned wire.  Because perms, replay and pricing all read
    ONE table, step drift (the PR 4 floor-vs-ceil class) is structurally
    impossible.  ``raw`` sums the same entries' uncompressed-equivalent
    (unpadded) payloads: what the lax.* collective would move.
    """
    cap, entry_wire, entry_raw = _entry_pricers(
        op, algo, n_elems, n, capacity_factor, chunks, codec)
    if n < 2:
        # Degenerate axis: the route table has no wire rounds.  Preserve
        # the historic provisioning: one full stream for the log-depth
        # ops (steps_for floors n at 2), zero for the rings.
        if (op == "allreduce" and algo == "redoub") or op == "broadcast":
            return cap, _stream_bytes(n_elems, capacity_factor, codec), \
                n_elems * 4
        if op == "all_to_all":
            h = schedule.Hop(0, 0, (0, 1), "lossy", "compressed")
            return cap, entry_wire(h), entry_raw(h)
        return cap, 0, 0
    table = schedule.build(op, algo, n)
    send = [0] * n
    send_raw = [0] * n
    for rnd in table.rounds:
        for h in rnd:
            send[h.sender] += entry_wire(h)
            send_raw[h.sender] += entry_raw(h)
    return cap, max(send), max(send_raw)


def _stream_elems(op, algo, n_elems, n, chunks):
    """Elements of one wire stream as the execute layer pads it: the
    payload a codec kernel sees (pipelined rings and chunked ops pad to
    whole-tile pieces, intring pads chunks to whole code rows)."""
    p = max(chunks, 1)
    chunk = -(-n_elems // max(n, 1))
    if op == "allreduce" and algo == "redoub" or op == "broadcast":
        return n_elems
    if op == "allreduce" and algo == "intring":
        return ops.n_blocks_for(chunk) * ops.BLOCK
    if op == "allreduce":  # float ring
        return _ring_piece_sizes(n_elems, n, chunks)[1]
    if op in ("reduce_scatter", "allgather"):
        own = chunk if op == "reduce_scatter" else n_elems
        if p > 1:
            quantum = p * _PIECE_QUANTUM
            return (-(-own // quantum) * quantum) // p
        return own
    if op in ("scatter", "all_to_all"):
        return chunk
    raise ValueError(f"unknown op {op!r}")


def _entry_pricers(op, algo, n_elems, n, capacity_factor, chunks, codec):
    """Per-table-entry pricing closures for one op's transport.

    Returns ``(capacity_words, entry_wire(h), entry_raw(h))``: the
    provisioned capacity of one wire stream, and the compressed /
    uncompressed-equivalent bytes one :class:`schedule.Hop` ships —
    including the execute layer's padding (``_stream_elems``).
    """
    p = max(chunks, 1)
    piece = _stream_elems(op, algo, n_elems, n, chunks)
    chunk_in = -(-n_elems // max(n, 1))
    if op == "allreduce" and algo == "intring":
        cap = capacity_words_for(piece, capacity_factor, ops.BLOCK)
        stream = _int_stream_bytes(piece, capacity_factor)
        return cap, (lambda h: stream), (lambda h: chunk_in * 4)
    cap = codecs.codec_capacity_words(codec, piece, capacity_factor)
    stream = _stream_bytes(piece, capacity_factor, codec)
    if op == "allreduce" and algo == "redoub" or op == "broadcast":
        return cap, (lambda h: stream), (lambda h: n_elems * 4)
    if op in ("allreduce", "reduce_scatter"):
        return cap, (lambda h: p * stream), (lambda h: chunk_in * 4)
    if op == "allgather":
        return cap, (lambda h: p * stream), (lambda h: n_elems * 4)
    if op == "scatter":
        # Trimmed-slab schedule: each entry ships one compressed stream
        # per REAL chunk in its slab, so the root's entries sum to
        # exactly n-1 chunk streams at ANY axis size (the padded virtual
        # tree's zero-padding chunks never appear in the table).
        return cap, (lambda h: h.chunk_slab[1] * stream), \
            (lambda h: h.chunk_slab[1] * piece * 4)
    return cap, (lambda h: stream), (lambda h: piece * 4)  # all_to_all


def _walk_note(op, algo, n_elems, n, chunks, codec, fused):
    """The ``Plan.notes`` entry naming the wire-stream walk the plan's
    codec kernels take (``lorenzo.rows_per_step`` of one stream's padded
    blocks); none where no stream kernel runs: a 1-rank axis, the
    unfused oracle path, intring's integer codes, or a codec provisioned
    structurally (passthrough)."""
    if (n < 2 or not fused or algo == "intring"
            or codecs.get_codec(codec).capacity_words is not None):
        return ()
    nb = ops.n_blocks_for(_stream_elems(op, algo, n_elems, n, chunks))
    return (f"codec walk: {ops.rows_per_step(nb)} block rows per grid step "
            f"({nb} blocks a stream)",)


def assert_step_count_consistency(n_range=range(2, 34), n_elems: int = 4096,
                                  capacity_factor: float = 0.6) -> None:
    """Structural self-check: the wire accounting's implied step counts
    equal ``cost_model.steps_for`` for every axis size in ``n_range`` —
    the PR 4 floor-vs-ceil regression (plans silently under-reported
    non-power-of-two wire bytes while the cost model used ceil, so
    planning could mis-rank algorithms) — and the trimmed-slab schedule
    is well-formed (ISSUE 5): for every n the slab table's root streams
    sum to exactly n-1 chunks, every non-root rank receives exactly once,
    each exchanged slab is exactly the real ranks of the receiver's
    virtual subtree, at most one trimmed exchange per round (the "one
    extra ppermute shape"), none at power-of-two n, and the scatter wire
    accounting prices exactly those root slabs.  Raises AssertionError
    naming the first disagreeing (op, n).  Called by tests/test_comm.py
    and, on every CI run, by benchmarks/regression_check.py.  Raises
    explicitly (not via ``assert`` statements, which vanish under
    ``python -O`` — this is the check that must never silently pass).
    """
    def _require(cond, msg):
        if not cond:
            raise AssertionError(msg)

    stream = _stream_bytes(n_elems, capacity_factor)
    for n in n_range:
        ceil_steps = max(n - 1, 1).bit_length()
        for algo in ("redoub", "binomial"):
            _require(cost_model.steps_for(algo, n) == ceil_steps,
                     f"steps_for({algo!r}, {n}) != ceil(log2 n)")
        _, wire, raw = _wire_accounting(
            "allreduce", "redoub", n_elems, n, capacity_factor, 1)
        _require(wire == ceil_steps * stream,
                 f"redoub wire accounting disagrees with the cost model at n={n}")
        _require(raw == ceil_steps * n_elems * 4, f"redoub raw bytes at n={n}")
        _, wire, _ = _wire_accounting(
            "broadcast", "binomial", n_elems, n, capacity_factor, 1)
        _require(wire == ceil_steps * stream,
                 f"broadcast wire accounting disagrees with the cost model at n={n}")

        # Trimmed-slab schedule well-formedness (the scatter tree).
        table = cost_model.binomial_slab_table(n)
        _require(len(table) == ceil_steps,
                 f"slab table has {len(table)} rounds != ceil(log2 {n})")
        receivers = []
        for span, full, trim in table:
            _require(trim is None or 0 < trim[2] < span,
                     f"trimmed slab out of range at n={n}, span={span}")
            if n & (n - 1) == 0:
                _require(trim is None,
                         f"power-of-two n={n} must have no trimmed exchange")
            pairs = [(i, i + span, span) for i in full]
            if trim is not None:
                pairs.append(trim)
            for snd, rcv, slab in pairs:
                receivers.append(rcv)
                _require(
                    slab == max(0, min(n, rcv + span) - rcv),
                    f"slab != real ranks of subtree [{rcv},{rcv + span}) "
                    f"at n={n}")
        _require(sorted(receivers) == list(range(1, n)),
                 f"slab table receivers != every non-root rank at n={n}")
        root_streams = cost_model.scatter_root_chunk_streams(n)
        _require(root_streams == n - 1,
                 f"root slab-sum {root_streams} != n-1 chunks at n={n}")
        chunk = -(-n_elems // n)
        _, wire, _ = _wire_accounting(
            "scatter", "binomial", n_elems, n, capacity_factor, 1)
        _require(
            wire == root_streams * _stream_bytes(chunk, capacity_factor),
            f"scatter wire accounting disagrees with the trimmed slab "
            f"table at n={n}")


def _eb_stage(op, algo, eb, n, worst_case):
    if op == "allreduce":
        if algo == "intring":
            return eb  # single quantization grid; n addends share it
        key = f"allreduce_{algo}"
        return error_budget.allocate(eb, key, n, worst_case=worst_case)
    if op == "reduce_scatter":
        return error_budget.allocate(
            eb, "reduce_scatter_ring", n, worst_case=worst_case
        )
    return eb  # data-movement ops: exactly one lossy hop


# ---------------------------------------------------------------------------
# Algorithm selection (the paper's §3.3.3 design framework)
#
# Moved here from core/selector.py (now a deprecation shim): the policy
# registry below is the ONLY selection authority, and these are its cost
# evaluators.
# ---------------------------------------------------------------------------


def select_allreduce(
    d_bytes: int,
    n_ranks: int,
    ratio: float = 20.0,
    hw: cost_model.Hardware = cost_model.TPU_V5E,
    *,
    allow_beyond_paper: bool = False,
) -> str:
    """Return 'ring' | 'redoub' (| 'intring' when beyond-paper allowed).

    The PAPER's selector (§3.3.3): with GPU compression in the loop the
    classic "ring for large messages" rule inverts once the per-chunk
    size D/N falls below the compressor's saturation point; recursive
    doubling's log2(N) *saturated* compressions then win despite moving
    more bytes.  Both algorithms are costed under the paper's two-kernel
    multi-stream-overlap models (no fused hop on either side —
    ``allreduce_ring_gz`` has none, so redoub must not get one either or
    the crossover is biased).  The production planner with the fused-hop
    schedule is :func:`select_allreduce_plan`.  A conservative default
    compression ratio of 20x (paper Table 1 sees 46-94x on RTM data) is
    used unless the caller passes a measured one.
    """
    costs = {
        "ring": cost_model.allreduce_ring_gz(d_bytes, n_ranks, ratio, hw),
        "redoub": cost_model.allreduce_redoub_gz(
            d_bytes, n_ranks, ratio, hw, fused_hop=False
        ),
    }
    if allow_beyond_paper:
        costs["intring"] = cost_model.allreduce_intring_gz(
            d_bytes, n_ranks, ratio, hw)
    return min(costs, key=costs.get)


def select_allreduce_plan(
    d_bytes: int,
    n_ranks: int,
    ratio: float = 20.0,
    hw: cost_model.Hardware = cost_model.TPU_V5E,
    *,
    allow_beyond_paper: bool = False,
    chunk_candidates=cost_model.PIPELINE_CHUNK_CANDIDATES,
    fused_hop: bool = True,
) -> tuple:
    """Pick (algo, pipeline_chunks) from the explicit per-chunk cost model.

    Ring is costed under the chunked double-buffered schedule at its best
    chunk count (DESIGN.md §4): above the compressor saturation size the
    pipelined ring strictly dominates the sequential one, so the plan
    comes back with chunks > 1; below it, per-piece overhead wins and the
    plan degrades to the sequential schedule (chunks == 1).  ReDoub
    compresses full messages — its overlap is already a single long
    chain, so it takes no chunk knob (returned chunks apply to ring
    only).  ``fused_hop`` costs BOTH algorithms' hops as single-pass
    ``t_hop_fused`` kernels and pushes the ring's best chunk count
    deeper.
    """
    ring_chunks = cost_model.best_pipeline_chunks(
        d_bytes, n_ranks, ratio, hw, chunk_candidates, fused_hop=fused_hop
    )
    costs = {
        ("ring", ring_chunks): cost_model.allreduce_ring_gz_chunked(
            d_bytes, n_ranks, ratio, hw, ring_chunks, fused_hop=fused_hop
        ),
        ("redoub", 1): cost_model.allreduce_redoub_gz(
            d_bytes, n_ranks, ratio, hw, fused_hop=fused_hop
        ),
    }
    if allow_beyond_paper:
        costs[("intring", 1)] = cost_model.allreduce_intring_gz(
            d_bytes, n_ranks, ratio, hw
        )
    return min(costs, key=costs.get)


# ---------------------------------------------------------------------------
# Policy registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlanRequest:
    """Everything a policy may inspect when choosing (algo, chunks)."""

    op: str
    n_elems: int
    nbytes: int
    axis_size: int
    requested_algo: Optional[str]  # None == "pick for me"
    requested_chunks: int          # 0 == "plan the ring depth for me"
    fused_hop: bool
    ratio: float                   # assumed compression ratio for costing
    hw: cost_model.Hardware


PolicyFn = Callable[[PlanRequest], tuple]
_POLICIES: dict = {}


def register_policy(name: str, fn: PolicyFn) -> None:
    """Add/replace a named plan policy: fn(PlanRequest) -> (algo, chunks)."""
    _POLICIES[name] = fn


def policy_names() -> tuple:
    return tuple(sorted(_POLICIES))


def _ring_depth(req: PlanRequest) -> int:
    from repro.core.collectives import plan_ring_pipeline_chunks

    return plan_ring_pipeline_chunks(
        req.n_elems, req.axis_size, ratio=req.ratio, hw=req.hw,
        fused_hop=req.fused_hop,
    )


def _data_movement_plan(req: PlanRequest):
    """(algo, chunks) for the fixed-algorithm data-movement ops — shared
    by every policy (the algorithm choice only exists for allreduce).

    ``requested_chunks == 0`` asks for planned depth (the grad-sync
    routing convention): the scatter gets it from
    ``cost_model.best_scatter_pipeline_chunks`` (the previously dead
    ``scatter_binomial_gz_chunked`` path — ISSUE 5 satellite); the other
    data movers have no modeled pipelined schedule and stay sequential.
    """
    chunks = req.requested_chunks
    if req.op == "scatter" and chunks == 0:
        chunks = cost_model.best_scatter_pipeline_chunks(
            req.nbytes, req.axis_size, req.ratio, req.hw
        )
    return _OP_ALGO[req.op], max(chunks, 1)


def _policy_auto(req: PlanRequest):
    """Production default — the selection gz_allreduce(algo="auto") ran.

    Algorithm from the fused-hop chunked cost model; ring pipeline depth
    from ``best_pipeline_chunks`` capped by whole-tile fill.  An explicit
    requested algo or depth is always honored; ``requested_chunks == 0``
    asks for the planned ring depth even under an explicit ring (the
    grad-sync routing convention).
    """
    if req.op != "allreduce":
        return _data_movement_plan(req)
    algo, chunks = req.requested_algo, req.requested_chunks
    if algo is None:
        algo, _ = select_allreduce_plan(
            req.nbytes, req.axis_size, req.ratio, req.hw,
            fused_hop=req.fused_hop,
        )
        if algo == "ring" and chunks in (0, 1):
            chunks = _ring_depth(req)
    elif algo == "ring" and chunks == 0:
        chunks = _ring_depth(req)
    return algo, max(chunks, 1)


def _policy_paper(req: PlanRequest):
    """The paper's §3.3.3 crossover: two-kernel cost models, sequential
    schedule — what the published figures compare.  Sequential applies to
    every op: unlike the other policies, an auto-depth request
    (``requested_chunks == 0``) does NOT resolve a pipelined scatter."""
    if req.op != "allreduce":
        return _OP_ALGO[req.op], max(req.requested_chunks, 1)
    algo = req.requested_algo
    if algo is None:
        algo = select_allreduce(req.nbytes, req.axis_size, req.ratio, req.hw)
    return algo, max(req.requested_chunks, 1)


def _policy_throughput(req: PlanRequest):
    """Fastest modeled plan, beyond-paper algorithms allowed.

    Same explicit-knob contract as ``auto``: a requested algorithm or
    depth is honored verbatim; only ``requested_chunks == 0`` (or an
    auto-resolved ring at the default depth) triggers depth planning.
    """
    if req.op != "allreduce":
        return _data_movement_plan(req)
    algo, chunks = req.requested_algo, req.requested_chunks
    if algo is None:
        algo, _ = select_allreduce_plan(
            req.nbytes, req.axis_size, req.ratio, req.hw,
            allow_beyond_paper=True, fused_hop=req.fused_hop,
        )
        if algo == "ring" and chunks in (0, 1):
            chunks = _ring_depth(req)
    elif algo == "ring" and chunks == 0:
        chunks = _ring_depth(req)
    return algo, max(chunks, 1)


def _policy_accuracy(req: PlanRequest):
    """Bitwise rank-consistent integer ring: one quantization grid, no
    stacked requantization noise (core/collectives.py consistency note)."""
    if req.op != "allreduce":
        return _data_movement_plan(req)
    return req.requested_algo or "intring", max(req.requested_chunks, 1)


register_policy("auto", _policy_auto)
register_policy("paper", _policy_paper)
register_policy("throughput", _policy_throughput)
register_policy("accuracy", _policy_accuracy)


# ---------------------------------------------------------------------------
# Memoized plan resolution
# ---------------------------------------------------------------------------

_PLAN_CACHE: dict = {}
_PLAN_STATS = {"hits": 0, "misses": 0}
# Per-codec-key hit/miss counters ("auto" is its own bucket: the REQUESTED
# codec is the cache identity; the resolved one lives on the Plan).
_PLAN_STATS_BY_CODEC: dict = {}


def _codec_stat(codec: str, field: str) -> None:
    rec = _PLAN_STATS_BY_CODEC.setdefault(codec, {"hits": 0, "misses": 0})
    rec[field] += 1


# Per-op hit/miss counters (op is key[0] of both caches).  The bucketed
# grad sync resolves one plan per (op, bucket shape) and re-hits it every
# step — by_op is how tests pin "K buckets -> K allreduce entries, all
# later traces pure hits" without parsing raw key tuples (ISSUE 9).
_PLAN_STATS_BY_OP: dict = {}


def _op_stat(op: str, field: str) -> None:
    rec = _PLAN_STATS_BY_OP.setdefault(op, {"hits": 0, "misses": 0})
    rec[field] += 1


def plan_cache_stats() -> dict:
    """{'hits', 'misses', 'entries', 'keys', 'by_codec', ...} —
    observability for tests and the acceptance criterion "exactly one
    cache entry per distinct (op, nbytes, dtype, axis_size, eb, codec)".

    ``by_codec`` breaks hits/misses AND entry counts (both the flat and
    the hier plan cache — the codec is the last key component of each)
    down by the requested codec key, so a test can pin
    one-entry-per-(op, codec) without parsing raw key tuples.

    ``by_op`` is the same breakdown keyed on the op (key[0] of both
    caches) — the bucketed grad sync's cache-growth contract ("one entry
    per bucket shape, every later step a hit") reads directly off it.
    """
    by_codec = {}
    for c, rec in _PLAN_STATS_BY_CODEC.items():
        by_codec[c] = {
            "hits": rec["hits"],
            "misses": rec["misses"],
            "entries": sum(1 for k in _PLAN_CACHE if k[-1] == c),
            "hier_entries": sum(1 for k in _HIER_PLAN_CACHE if k[-1] == c),
        }
    by_op = {}
    for o, rec in _PLAN_STATS_BY_OP.items():
        by_op[o] = {
            "hits": rec["hits"],
            "misses": rec["misses"],
            "entries": sum(1 for k in _PLAN_CACHE if k[0] == o),
            "hier_entries": sum(1 for k in _HIER_PLAN_CACHE if k[0] == o),
        }
    return {
        "hits": _PLAN_STATS["hits"],
        "misses": _PLAN_STATS["misses"],
        "entries": len(_PLAN_CACHE),
        "keys": tuple(_PLAN_CACHE),
        "hier_entries": len(_HIER_PLAN_CACHE),
        "hier_keys": tuple(_HIER_PLAN_CACHE),
        "by_codec": by_codec,
        "by_op": by_op,
    }


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()
    _HIER_PLAN_CACHE.clear()
    _COMM_CACHE.clear()  # the memoized one-shot communicators, too
    _PLAN_STATS["hits"] = 0
    _PLAN_STATS["misses"] = 0
    _PLAN_STATS_BY_CODEC.clear()
    _PLAN_STATS_BY_OP.clear()


def _codec_adjusted(codec, ratio, hw):
    """(effective_ratio, adjusted_hw, codec_fused_hop) for pricing a codec.

    Calibrated per-codec terms on the Hardware (``hw.terms_for``, fitted
    by :func:`fit_codec_terms`) win over the registry's modeled defaults.
    Identity terms short-circuit to the caller's own (ratio, hw) — the
    default ``lorenzo`` entry ships identity terms, so an uncalibrated
    default plan prices bit-for-bit as it did before the registry.
    """
    spec = codecs.get_codec(codec)
    terms = hw.terms_for(codec) or spec.terms
    if terms == cost_model.CodecTerms(codec):
        return ratio, hw, spec.fused_hop
    return terms.effective_ratio(ratio), terms.apply(hw), spec.fused_hop


def _op_model_time(op, algo, nbytes, n, ratio, hw, chunks, fused_hop):
    """Modeled seconds of one collective under (algo, ratio, hw) — the
    per-op comparator ``codec='auto'`` ranks candidates with.  Allreduce
    and the modeled data movers use the cost model's own functions; the
    remaining ops are priced from the primitive compress/net/decompress
    terms (coarse, but the comparison only needs to order codecs whose
    ratio and throughput terms differ)."""
    if n <= 1:
        return 0.0
    if op == "allreduce":
        return _allreduce_model_time(algo, nbytes, n, ratio, hw, chunks,
                                     fused_hop)
    if op == "scatter":
        return cost_model.scatter_binomial_gz_chunked(
            nbytes, n, ratio, hw, max(chunks, 1)
        )
    if op == "allgather":
        return cost_model.allgather_ring_gz(nbytes, n, ratio, hw)
    if op == "broadcast":
        steps = cost_model.steps_for("binomial", n)
        return (cost_model.t_compress(nbytes, hw)
                + steps * cost_model.t_net(nbytes / ratio, hw)
                + cost_model.t_decompress(nbytes, hw))
    chunk = nbytes / n
    if op == "reduce_scatter":
        return (n - 1) * (cost_model.t_compress(chunk, hw)
                          + cost_model.t_net(chunk / ratio, hw)
                          + cost_model.t_decompress(chunk, hw))
    # all_to_all: compress/decompress the whole payload, n exchange lanes.
    return (cost_model.t_compress(nbytes, hw)
            + n * cost_model.t_net(chunk / ratio, hw)
            + cost_model.t_decompress(nbytes, hw))


# Policies that rank algorithms by modeled time — the only ones where
# ranking CODECS by the same model is meaningful (paper reproduces the
# published selector; accuracy pins the integer ring).
_CODEC_AUTO_POLICIES = ("auto", "throughput")


def _resolve_codec(op, policy, policy_fn, req, codec):
    """(codec, algo, chunks, codec_ratio, fused_hop, notes) — one place
    owns every codec-resolution rule so ``_resolve_plan`` stays linear:

      * explicit codec: price the policy under its adjusted (ratio, hw);
      * ``auto`` under an auto/throughput policy: run the policy per
        candidate and argmin the per-op modeled time;
      * ``auto`` under other policies: default codec, with a note;
      * ``intring`` ships its own integer wire format: codec forced back
        to ``lorenzo`` (noted);
      * codecs without a fused hop kernel downgrade ``fused_hop`` (noted).
    """
    notes = []
    if codec == codecs.AUTO:
        if policy in _CODEC_AUTO_POLICIES:
            best = None
            for cand in codecs.auto_codecs():
                eff_ratio, hw_c, cand_fh = _codec_adjusted(
                    cand, req.ratio, req.hw
                )
                fh = req.fused_hop and cand_fh
                req_c = dataclasses.replace(
                    req, fused_hop=fh, ratio=eff_ratio, hw=hw_c
                )
                algo_c, chunks_c = policy_fn(req_c)
                t = _op_model_time(
                    op, algo_c, req.nbytes, req.axis_size, eff_ratio, hw_c,
                    chunks_c, fh,
                )
                if best is None or t < best[0]:
                    best = (t, cand, algo_c, chunks_c, eff_ratio)
            _, codec, algo, chunks, codec_ratio = best
            notes.append(
                f"codec auto->{codec!r} (fastest modeled {op} of "
                f"{codecs.auto_codecs()})"
            )
        else:
            codec = "lorenzo"
            notes.append(
                f"codec auto->'lorenzo' (policy {policy!r} does not rank "
                "codecs by modeled time)"
            )
            codec_ratio, hw_c, cand_fh = _codec_adjusted(
                codec, req.ratio, req.hw
            )
            req_c = dataclasses.replace(
                req, fused_hop=req.fused_hop and cand_fh, ratio=codec_ratio,
                hw=hw_c,
            )
            algo, chunks = policy_fn(req_c)
    else:
        codec_ratio, hw_c, cand_fh = _codec_adjusted(codec, req.ratio, req.hw)
        req_c = dataclasses.replace(
            req, fused_hop=req.fused_hop and cand_fh, ratio=codec_ratio,
            hw=hw_c,
        )
        algo, chunks = policy_fn(req_c)
    if algo == "intring" and codec != "lorenzo":
        notes.append(
            f"codec {codec!r}->'lorenzo' (intring ships its own integer "
            "wire format)"
        )
        codec = "lorenzo"
        codec_ratio, _, _ = _codec_adjusted(codec, req.ratio, req.hw)
    spec = codecs.get_codec(codec)
    fused_hop = req.fused_hop and spec.fused_hop
    if req.fused_hop and not spec.fused_hop:
        notes.append(
            f"fused_hop off (codec {codec!r} has no fused "
            "unpack+reduce+repack kernel; hops run the two-pass "
            "composition)"
        )
    return codec, algo, max(chunks, 1), codec_ratio, fused_hop, tuple(notes)


def _resolve_plan(
    op, n_elems, dtype, axis_size, eb, *, policy, requested_algo,
    requested_chunks, capacity_factor, worst_case_budget, fused, fused_hop,
    ratio, hw, on_overflow="flag", verify_streams=False, codec="lorenzo",
) -> Plan:
    key = (
        # The canonical identity of a plan...
        op, n_elems * 4, str(dtype), axis_size, eb,
        # ...plus the communicator knobs that parameterize resolution.
        policy, requested_algo, requested_chunks, capacity_factor,
        worst_case_budget, fused, fused_hop, ratio, hw,
        on_overflow, verify_streams,
        # The codec is appended LAST: existing tests pin key prefixes, and
        # plan_cache_stats' by_codec breakdown reads key[-1].
        codec,
    )
    hit = _PLAN_CACHE.get(key)
    if hit is not None:
        _PLAN_STATS["hits"] += 1
        _codec_stat(codec, "hits")
        _op_stat(op, "hits")
        return hit
    _PLAN_STATS["misses"] += 1
    _codec_stat(codec, "misses")
    _op_stat(op, "misses")
    if op not in OPS:
        raise ValueError(f"unknown collective op {op!r}")
    try:
        policy_fn = _POLICIES[policy]
    except KeyError:
        raise ValueError(
            f"unknown policy {policy!r}; registered: {policy_names()}"
        ) from None
    req = PlanRequest(
        op=op, n_elems=n_elems, nbytes=n_elems * 4, axis_size=axis_size,
        requested_algo=requested_algo, requested_chunks=requested_chunks,
        fused_hop=fused_hop, ratio=ratio, hw=hw,
    )
    codec, algo, chunks, codec_ratio, fused_hop, notes = _resolve_codec(
        op, policy, policy_fn, req, codec
    )
    cap, wire, raw = _wire_accounting(
        op, algo, n_elems, axis_size, capacity_factor, chunks, codec
    )
    plan = Plan(
        op=op, algo=algo, n_elems=n_elems, nbytes=n_elems * 4,
        dtype=str(dtype), axis_size=axis_size, eb=eb,
        eb_stage=_eb_stage(op, algo, eb, axis_size, worst_case_budget),
        pipeline_chunks=chunks, fused=fused, fused_hop=fused_hop,
        capacity_factor=capacity_factor, worst_case_budget=worst_case_budget,
        capacity_words=cap, wire_bytes=wire,
        ratio=(raw / wire) if wire else 1.0, policy=policy,
        slab_table=(cost_model.binomial_slab_table(axis_size)
                    if algo == "binomial" else ()),
        on_overflow=on_overflow, verify_streams=verify_streams,
        fallback=_fallback_plan(op, n_elems, axis_size, hw),
        codec=codec, codec_ratio=codec_ratio,
        notes=notes + _walk_note(op, algo, n_elems, axis_size, chunks, codec,
                                 fused),
        route_table=(schedule.build(op, algo, axis_size)
                     if axis_size >= 2 else None),
    )
    _PLAN_CACHE[key] = plan
    return plan


# ---------------------------------------------------------------------------
# Two-level (node × intra-node) plan resolution
# ---------------------------------------------------------------------------

_HIER_PLAN_CACHE: dict = {}


def _allreduce_model_time(algo, nbytes, n, ratio, hw, chunks, fused_hop):
    """Modeled seconds of one single-axis compressed allreduce — the same
    cost functions the policies rank, evaluated for a resolved plan."""
    if n <= 1:
        return 0.0
    if algo == "redoub":
        return cost_model.allreduce_redoub_gz(
            nbytes, n, ratio, hw, fused_hop=fused_hop
        )
    if algo == "intring":
        return cost_model.allreduce_intring_gz(nbytes, n, ratio, hw)
    return cost_model.allreduce_ring_gz_chunked(
        nbytes, n, ratio, hw, chunks, fused_hop=fused_hop
    )


def _resolve_hier_plan(
    op, n_elems, dtype, topology, eb, *, policy, requested_algo,
    requested_chunks, capacity_factor, worst_case_budget, fused, fused_hop,
    ratio, hw, on_overflow="flag", verify_streams=False, codec="lorenzo",
) -> HierPlan:
    """Resolve the frozen two-level plan for ``topology = (n_nodes, L)``.

    The cache keys on the FULL topology tuple: the same composite axis
    names over a reshaped mesh (2×4 vs 4×2) resolve different schedules —
    different shard sizes, different inter fan-out — so they must replan
    (the PR 3 multi-mesh lesson, extended to 2D).

    Resolution rule:

      * ``L == 1`` (one rank per node) or no link asymmetry
        (``hw.link_asymmetry() <= 1``): FLAT — there is no fast link to
        exploit, and running the composite-axis single-axis schedule
        keeps the result bitwise-identical to the pre-hierarchy path (the
        degenerate-topology property tests pin exactly this).
      * Otherwise compare modeled times: the flat compressed allreduce
        over N ranks (every link priced at the inter terms — a flat plan
        is topology-blind, and its node-boundary ranks really do cross on
        every send in node-major order) vs
        ``cost_model.allreduce_hier_gz``.  The policy picks the inter
        stage's algorithm/depth by resolving an ordinary sub-plan at the
        shard size over ``n_nodes`` ranks.
    """
    topology = (int(topology[0]), int(topology[1]))
    key = (
        op, n_elems * 4, str(dtype), topology, eb,
        policy, requested_algo, requested_chunks, capacity_factor,
        worst_case_budget, fused, fused_hop, ratio, hw,
        on_overflow, verify_streams,
        codec,  # appended LAST, like the flat cache (by_codec reads k[-1])
    )
    hit = _HIER_PLAN_CACHE.get(key)
    if hit is not None:
        _PLAN_STATS["hits"] += 1
        _codec_stat(codec, "hits")
        _op_stat(op, "hits")
        return hit
    _PLAN_STATS["misses"] += 1
    _codec_stat(codec, "misses")
    _op_stat(op, "misses")
    if op != "allreduce":
        raise ValueError(
            f"hierarchical plans support op='allreduce' only; got {op!r}"
        )
    n_nodes, L = topology
    N = n_nodes * L
    nbytes = n_elems * 4
    knobs = dict(
        policy=policy, requested_algo=requested_algo,
        requested_chunks=requested_chunks, capacity_factor=capacity_factor,
        worst_case_budget=worst_case_budget, fused=fused,
        fused_hop=fused_hop, ratio=ratio, hw=hw,
        on_overflow=on_overflow, verify_streams=verify_streams,
        codec=codec,
    )
    flat_plan = _resolve_plan(op, n_elems, dtype, N, eb, **knobs)
    # Price the flat-vs-hier comparison at the RESOLVED codec's terms
    # (identity for the default, so the pre-registry comparison is
    # bit-for-bit unchanged).
    flat_ratio, flat_hw, _ = _codec_adjusted(flat_plan.codec, ratio, hw)
    t_flat = _allreduce_model_time(
        flat_plan.algo, nbytes, N, flat_ratio, flat_hw,
        flat_plan.pipeline_chunks, flat_plan.fused_hop,
    )

    inter = None
    t_hier = float("inf")
    shard_elems = -(-n_elems // L)
    if L > 1 and hw.link_asymmetry() > 1.0:
        # Only the inter-node stage is lossy; the exact intra stages get 0.
        eb_inter = error_budget.split_lossy(
            eb, (False, n_nodes > 1, False)
        )[1]
        if n_nodes > 1:
            inter = _resolve_plan(
                op, shard_elems, dtype, n_nodes, eb_inter, **knobs
            )
        inter_ratio, inter_hw, _ = _codec_adjusted(
            inter.codec if inter else "lorenzo", ratio, hw
        )
        t_hier = cost_model.allreduce_hier_gz(
            nbytes, n_nodes, L, inter_ratio, inter_hw,
            inter_algo=inter.algo if inter else "ring",
            chunks=inter.pipeline_chunks if inter else 1,
            fused_hop=inter.fused_hop if inter else fused_hop,
        )

    flat = t_flat <= t_hier
    if flat:
        inter = None
        intra_wire = 0
        inter_wire = flat_plan.wire_bytes  # boundary rank: every send crosses
        t_model = t_flat
    else:
        intra_wire = 2 * (L - 1) * shard_elems * 4
        inter_wire = inter.wire_bytes if inter else 0
        t_model = t_hier
    route = (flat_plan.route_table if flat else schedule.build_hier(
        n_nodes, L, inter.algo if inter else "ring"))
    plan = HierPlan(
        op=op, topology=topology, n_elems=n_elems, nbytes=nbytes,
        dtype=str(dtype), eb=eb, flat=flat,
        inter=inter, flat_plan=flat_plan,
        intra_wire_bytes=0 if flat else intra_wire,
        inter_wire_bytes=inter_wire, t_model=t_model, t_flat=t_flat,
        policy=policy,
        on_overflow=on_overflow, verify_streams=verify_streams,
        fallback=_fallback_plan(op, n_elems, N, hw),
        codec=(flat_plan.codec if flat
               else (inter.codec if inter else "lorenzo")),
        route_table=route,
    )
    _HIER_PLAN_CACHE[key] = plan
    return plan


# ---------------------------------------------------------------------------
# Differentiable all-to-all on a frozen plan
# ---------------------------------------------------------------------------
#
# The rank-exchange layout is self-inverse (chunk r of rank p lands at rank
# r, slot p), so the transpose is the same exchange applied to the
# cotangent — compressed too, straight-through the quantizer.  The Plan is
# hashable, hence a valid nondiff argument.


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _a2a_planned(x, axis_name, plan: Plan):
    from repro.core.collectives import _execute_all_to_all

    return _execute_all_to_all(x, axis_name, plan.as_config())


def _a2a_planned_fwd(x, axis_name, plan):
    return _a2a_planned(x, axis_name, plan), None


def _a2a_planned_bwd(axis_name, plan, _, g):
    g_out, _g_ovf = g
    return (_a2a_planned(g_out, axis_name, plan)[0],)


_a2a_planned.defvjp(_a2a_planned_fwd, _a2a_planned_bwd)


# ---------------------------------------------------------------------------
# Health counters (observable outside the trace, like the plan-cache stats)
# ---------------------------------------------------------------------------
#
# Per-(op, axis) counts of calls / overflow events / non-finite events /
# fallback executions, accumulated host-side via jax.debug.callback from
# rank 0 of each collective (once per call, not once per rank).  OFF by
# default: the enable flag is read at TRACE time, so traces built while
# tracking is disabled carry no callback at all (zero overhead), and
# functions jitted under `enable_health_tracking()` keep emitting until
# re-traced.  Call `jax.effects_barrier()` before reading if the enclosing
# computation may still be in flight.

_HEALTH: dict = {}
_HEALTH_ENABLED = False


def enable_health_tracking(enabled: bool = True) -> None:
    """Toggle per-communicator health counters (trace-time gate)."""
    global _HEALTH_ENABLED
    _HEALTH_ENABLED = enabled


def health_stats() -> dict:
    """{(op, axis_repr): {'calls', 'overflow', 'nonfinite', 'fallbacks'}}"""
    return {k: dict(v) for k, v in _HEALTH.items()}


def clear_health_stats() -> None:
    _HEALTH.clear()


def _health_cb(key, is_r0, ovf, nonfinite, fell_back):
    if not bool(is_r0):
        return
    rec = _HEALTH.setdefault(
        key, {"calls": 0, "overflow": 0, "nonfinite": 0, "fallbacks": 0}
    )
    rec["calls"] += 1
    rec["overflow"] += int(bool(ovf))
    rec["nonfinite"] += int(bool(nonfinite))
    rec["fallbacks"] += int(bool(fell_back))


def _emit_health(op, axis_name, overflow, nonfinite, fell_back) -> None:
    if not _HEALTH_ENABLED:
        return
    from repro.core.collectives import _axis_rank

    jax.debug.callback(
        partial(_health_cb, (op, repr(axis_name))),
        _axis_rank(axis_name) == 0, overflow, nonfinite, fell_back,
    )


def _raise_degraded(what, ovf, nonfinite):
    """Host side of ``on_overflow="raise"``.  Runs as an ``io_callback``:
    an exception raised in a ``jax.debug.callback`` is only logged, while
    one raised here fails the computation (``JaxRuntimeError``)."""
    if bool(ovf) or bool(nonfinite):
        raise RuntimeError(
            f"gZ collective degraded ({what}): overflow={bool(ovf)} "
            f"nonfinite={bool(nonfinite)} — a compressed stream exceeded "
            "its provisioned capacity (or failed verification) or the "
            "input held NaN/Inf.  Use on_overflow='fallback' for in-trace "
            "lossless recovery, or 'flag' to only report."
        )


# ---------------------------------------------------------------------------
# The communicator
# ---------------------------------------------------------------------------


class GZCommunicator:
    """Resolve-once communicator bound to one mesh axis.

    Construct OUTSIDE the traced region with the static knobs; call the
    collective methods inside shard_map bodies.  ``axis_size`` may be
    passed explicitly (e.g. from the mesh shape) or left None to be read
    from the surrounding shard_map trace on first use — axis sizes are
    static either way, so plan resolution never touches a tracer.

    ``config`` is the same knob dataclass the legacy wrappers take
    (``GZConfig``): eb, capacity_factor, algo (``"auto"`` delegates to
    the policy), worst_case_budget, pipeline_chunks, fused, fused_hop.
    """

    def __init__(
        self,
        axis_name,
        *,
        config=None,
        policy: str = "auto",
        hw: cost_model.Hardware = cost_model.TPU_V5E,
        ratio: float = 20.0,
        axis_size: Optional[int] = None,
        _auto_depth: bool = False,
    ):
        from repro.core.collectives import GZConfig

        self.axis_name = axis_name
        self.config = config if config is not None else GZConfig()
        if policy not in _POLICIES:
            raise ValueError(
                f"unknown policy {policy!r}; registered: {policy_names()}"
            )
        self.policy = policy
        self.hw = hw
        self.ratio = ratio
        self._axis_size = axis_size
        # grad-sync routing convention: ring depth is planned even when the
        # algorithm was requested explicitly (requested_chunks == 0).
        self._auto_depth = _auto_depth

    # -- construction helpers ------------------------------------------------

    @classmethod
    def for_config(cls, axis_name, config, *, policy: str = "auto",
                   hw: cost_model.Hardware = cost_model.TPU_V5E,
                   ratio: float = 20.0, axis_size: Optional[int] = None,
                   auto_depth: bool = False) -> "GZCommunicator":
        """Memoized one-shot communicator — the legacy ``gz_*`` wrappers'
        entry point (one instance per distinct (axis, knobs))."""
        return _communicator_cache(
            cls, axis_name, config, policy, hw, ratio, axis_size, auto_depth
        )

    def calibrate(self, *, sizes=(1 << 16, 1 << 18, 1 << 20), reps: int = 3,
                  interpret: Optional[bool] = None,
                  network: Optional[dict] = None,
                  fit_codecs: bool = True) -> "GZCommunicator":
        """Return a communicator whose cost model is fitted to THIS host.

        Times the actual codec (``measure_codec``) at ``sizes`` elements
        and least-squares-fits the Hardware throughput/overhead terms the
        planner evaluates.  Network terms are kept from the current model
        unless ``network`` supplies measured ppermute timings per link
        class — ``{'inter': [(bytes, seconds), ...], 'intra': [...]}``
        (see :func:`measure_ppermute`) — in which case each named link's
        alpha-beta terms are least-squares-fitted too
        (:func:`fit_network`).

        With ``fit_codecs`` (the default) every registered wire codec is
        additionally timed on the same sample tensors
        (:func:`measure_codecs`) and its measured ratio/throughput written
        into per-codec ``Hardware.codec_terms`` — the terms
        ``codec='auto'`` ranks candidates with, so after calibration the
        auto/throughput policies pick the codec per tensor class from
        MEASURED collective time, not the registry's modeled defaults.
        """
        samples_c, samples_d = measure_codec(
            self.config, sizes=sizes, reps=reps, interpret=interpret
        )
        hw = fit_hardware(samples_c, samples_d, base=self.hw)
        for link, samples in (network or {}).items():
            hw = fit_network(samples, base=hw, link=link)
        if fit_codecs:
            hw = fit_codec_terms(
                measure_codecs(self.config, sizes=sizes, reps=reps), base=hw
            )
        return GZCommunicator(
            self.axis_name, config=self.config, policy=self.policy, hw=hw,
            ratio=self.ratio, axis_size=self._axis_size,
            _auto_depth=self._auto_depth,
        )

    # -- plan resolution -----------------------------------------------------

    def axis_size(self) -> int:
        """Static axis size: the bound value, or — when constructed with
        ``axis_size=None`` — the size read fresh from the surrounding
        shard_map trace at every call.  Never cached on the instance: a
        memoized ``for_config`` communicator outlives any one mesh, and
        the same axis name can be bound to different sizes across traces
        in one process."""
        if self._axis_size is not None:
            return self._axis_size
        from repro.core.collectives import _axis_size

        return int(_axis_size(self.axis_name))

    def plan(self, op: str, shape, dtype=jnp.float32) -> Plan:
        """Resolve the frozen Plan for ``op`` over a payload of ``shape``.

        ``shape`` is a shape tuple or an element count; resolution is a
        cache lookup after the first call with a given key (see
        :func:`plan_cache_stats`).
        """
        n_elems = int(np.prod(shape)) if not isinstance(shape, int) else shape
        cfg = self.config
        requested_algo = None if cfg.algo == "auto" else cfg.algo
        requested_chunks = cfg.pipeline_chunks
        if self._auto_depth and requested_chunks == 1:
            requested_chunks = 0
        return _resolve_plan(
            op, n_elems, jnp.dtype(dtype).name, self.axis_size(), cfg.eb,
            policy=self.policy, requested_algo=requested_algo,
            requested_chunks=requested_chunks,
            capacity_factor=cfg.capacity_factor,
            worst_case_budget=cfg.worst_case_budget, fused=cfg.fused,
            fused_hop=cfg.fused_hop, ratio=self.ratio, hw=self.hw,
            on_overflow=cfg.on_overflow, verify_streams=cfg.verify_streams,
            codec=cfg.codec,
        )

    # -- collectives ---------------------------------------------------------

    def _trivial(self, x) -> CollectiveResult:
        zero = jnp.zeros((), jnp.bool_)
        return CollectiveResult(x, zero, zero, 0, 1.0)

    def _finish(self, op, x, out, ovf, plan: Plan, *,
                root: int = 0) -> CollectiveResult:
        """Shared epilogue: global-OR the health bits, apply the plan's
        degradation policy (DESIGN.md §9), emit health counters.

        ``x`` is the (possibly poisoned) input the compressed schedule
        consumed — the fallback branch re-executes the LOSSLESS schedule
        over exactly that payload inside ``lax.cond`` (the predicate is
        psum-derived, hence replicated and cond-safe), so the recovered
        result is bitwise the uncompressed collective of the sanitized
        input.
        """
        from repro.core.collectives import (
            _axis_rank, _execute_lossless, _flags_across, _nonfinite_local,
        )

        nf_loc = _nonfinite_local(x)
        if op in ("scatter", "broadcast"):
            # Only the root's payload is significant; non-root junk must
            # not trip the non-finite guard.
            nf_loc &= _axis_rank(self.axis_name) == root
        overflow, nonfinite = _flags_across(ovf, nf_loc, self.axis_name)
        degraded = overflow | nonfinite
        fell_back = jnp.zeros((), jnp.bool_)
        if plan.on_overflow == "fallback":
            cfg = plan.as_config()
            out = lax.cond(
                degraded,
                lambda: _execute_lossless(
                    op, x, self.axis_name, cfg, root=root
                ),
                lambda: out,
            )
            fell_back = degraded
        elif plan.on_overflow == "raise":
            io_callback(
                partial(_raise_degraded, f"{op} over {self.axis_name!r}"),
                None, overflow, nonfinite,
            )
        _emit_health(op, self.axis_name, overflow, nonfinite, fell_back)
        return CollectiveResult(
            out, overflow, nonfinite, plan.wire_bytes, plan.ratio
        )

    def allreduce(self, x, *, plan: Optional[Plan] = None) -> CollectiveResult:
        """Compressed sum-allreduce of ``x`` over the bound axis."""
        if self.axis_size() == 1:
            return self._trivial(x)
        x = faults.maybe_poison_input(x, self.axis_name)
        plan = plan or self.plan("allreduce", x.shape, x.dtype)
        from repro.core.collectives import _execute_allreduce

        out, ovf = _execute_allreduce(x, self.axis_name, plan.as_config())
        return self._finish("allreduce", x, out, ovf, plan)

    def reduce_scatter(self, x, *, plan: Optional[Plan] = None) -> CollectiveResult:
        """Ring reduce-scatter: rank r returns summed chunk r (flat view)."""
        if self.axis_size() == 1:
            return self._trivial(x)
        x = faults.maybe_poison_input(x, self.axis_name)
        plan = plan or self.plan("reduce_scatter", x.shape, x.dtype)
        from repro.core.collectives import _execute_reduce_scatter

        out, ovf = _execute_reduce_scatter(x, self.axis_name, plan.as_config())
        return self._finish("reduce_scatter", x, out, ovf, plan)

    def allgather(self, x, *, plan: Optional[Plan] = None) -> CollectiveResult:
        """Ring allgather: compress once, forward compressed N-1 times."""
        if self.axis_size() == 1:
            return self._trivial(x)
        x = faults.maybe_poison_input(x, self.axis_name)
        plan = plan or self.plan("allgather", x.shape, x.dtype)
        from repro.core.collectives import _execute_allgather

        out, ovf = _execute_allgather(x, self.axis_name, plan.as_config())
        return self._finish("allgather", x, out, ovf, plan)

    def scatter(self, x_full, *, root: int = 0,
                plan: Optional[Plan] = None) -> CollectiveResult:
        """Binomial-tree compressed scatter from ``root`` (root 0 only)."""
        if self.axis_size() == 1:
            return self._trivial(x_full)
        x_full = faults.maybe_poison_input(x_full, self.axis_name)
        plan = plan or self.plan("scatter", x_full.shape, x_full.dtype)
        from repro.core.collectives import _execute_scatter

        out, ovf = _execute_scatter(
            x_full, self.axis_name, plan.as_config(), root=root
        )
        return self._finish("scatter", x_full, out, ovf, plan, root=root)

    def broadcast(self, x, *, root: int = 0,
                  plan: Optional[Plan] = None) -> CollectiveResult:
        """Binomial-tree broadcast: compress once at root."""
        if self.axis_size() == 1:
            return self._trivial(x)
        x = faults.maybe_poison_input(x, self.axis_name)
        plan = plan or self.plan("broadcast", x.shape, x.dtype)
        from repro.core.collectives import _execute_broadcast

        out, ovf = _execute_broadcast(
            x, self.axis_name, plan.as_config(), root=root
        )
        return self._finish("broadcast", x, out, ovf, plan, root=root)

    def all_to_all(self, x, *, plan: Optional[Plan] = None) -> CollectiveResult:
        """Compressed rank-exchange; differentiable (straight-through the
        quantizer, compressed cotangent — see ``_a2a_planned``)."""
        if self.axis_size() == 1:
            return self._trivial(x)
        x = faults.maybe_poison_input(x, self.axis_name)
        plan = plan or self.plan("all_to_all", x.shape, x.dtype)
        out, ovf = _a2a_planned(x, self.axis_name, plan)
        return self._finish("all_to_all", x, out, ovf, plan)

    def __repr__(self):
        return (
            f"GZCommunicator(axis={self.axis_name!r}, n={self._axis_size}, "
            f"policy={self.policy!r}, eb={self.config.eb}, hw={self.hw.name})"
        )


class GZHierCommunicator:
    """Resolve-once communicator bound to a two-level ``node × local``
    topology (DESIGN.md §8).

    ``node_axis`` is the slow (inter-node fabric) mesh axis; ``local_axis``
    is the fast intra-node axis — or a TUPLE of axes, all collapsed into
    "local" (grad-sync folds every non-node data-parallel axis in).
    ``topology`` may be passed explicitly as ``(n_nodes, gpus_per_node)``
    or left None to be read from the surrounding shard_map trace per call
    (sizes are static either way).

    ``allreduce`` dispatches on a frozen :class:`HierPlan`: per-link cost
    comparison decides flat vs hierarchical, the policy picks the inter
    stage's algorithm/compression depth, and the execute layer
    (``collectives._execute_allreduce_hier``) contains zero selector
    logic.  ``CollectiveResult.wire_bytes`` reports the INTER-NODE wire —
    the scarce resource this communicator exists to spend well.
    """

    def __init__(
        self,
        node_axis,
        local_axis,
        *,
        config=None,
        policy: str = "auto",
        hw: cost_model.Hardware = cost_model.TPU_V5E,
        ratio: float = 20.0,
        topology: Optional[tuple] = None,
        _auto_depth: bool = False,
    ):
        from repro.core.collectives import GZConfig

        self.node_axis = node_axis
        self.local_axis = (
            tuple(local_axis) if isinstance(local_axis, (tuple, list))
            else local_axis
        )
        self.config = config if config is not None else GZConfig()
        if policy not in _POLICIES:
            raise ValueError(
                f"unknown policy {policy!r}; registered: {policy_names()}"
            )
        self.policy = policy
        self.hw = hw
        self.ratio = ratio
        self._topology = tuple(topology) if topology is not None else None
        self._auto_depth = _auto_depth

    @classmethod
    def for_axes(cls, node_axis, local_axis, *, config=None,
                 policy: str = "auto",
                 hw: cost_model.Hardware = cost_model.TPU_V5E,
                 ratio: float = 20.0, topology: Optional[tuple] = None,
                 auto_depth: bool = False) -> "GZHierCommunicator":
        """Memoized one-shot hier communicator (one instance per distinct
        (axes, knobs) — cleared with :func:`clear_plan_cache`)."""
        local = (tuple(local_axis) if isinstance(local_axis, (tuple, list))
                 else local_axis)
        topo = tuple(topology) if topology is not None else None
        key = (cls, node_axis, local, config, policy, hw, ratio, topo,
               auto_depth)
        comm = _COMM_CACHE.get(key)
        if comm is None:
            comm = cls(
                node_axis, local, config=config, policy=policy, hw=hw,
                ratio=ratio, topology=topo, _auto_depth=auto_depth,
            )
            _COMM_CACHE[key] = comm
        return comm

    def topology(self) -> tuple:
        """Static ``(n_nodes, gpus_per_node)``: the bound tuple, or the
        sizes read fresh from the surrounding shard_map trace (never
        cached on the instance — a memoized communicator outlives any one
        mesh, and the same axis names can be bound to different shapes
        across traces: the 2×4-vs-4×2 replan case)."""
        if self._topology is not None:
            return self._topology
        from repro.core.collectives import _axis_size

        return (int(_axis_size(self.node_axis)),
                int(_axis_size(self.local_axis)))

    def _composite_axes(self) -> tuple:
        local = (self.local_axis if isinstance(self.local_axis, tuple)
                 else (self.local_axis,))
        return (self.node_axis,) + local

    def plan(self, shape, dtype=jnp.float32) -> HierPlan:
        """Resolve the frozen :class:`HierPlan` for an allreduce of
        ``shape`` over the bound topology (memoized on the full topology
        tuple plus the knob set)."""
        n_elems = int(np.prod(shape)) if not isinstance(shape, int) else shape
        cfg = self.config
        requested_algo = None if cfg.algo == "auto" else cfg.algo
        requested_chunks = cfg.pipeline_chunks
        if self._auto_depth and requested_chunks == 1:
            requested_chunks = 0
        return _resolve_hier_plan(
            "allreduce", n_elems, jnp.dtype(dtype).name, self.topology(),
            cfg.eb, policy=self.policy, requested_algo=requested_algo,
            requested_chunks=requested_chunks,
            capacity_factor=cfg.capacity_factor,
            worst_case_budget=cfg.worst_case_budget, fused=cfg.fused,
            fused_hop=cfg.fused_hop, ratio=self.ratio, hw=self.hw,
            on_overflow=cfg.on_overflow, verify_streams=cfg.verify_streams,
            codec=cfg.codec,
        )

    def allreduce(self, x, *, plan: Optional[HierPlan] = None) -> CollectiveResult:
        """Two-level compressed sum-allreduce over ``node × local``."""
        n_nodes, L = self.topology()
        if n_nodes * L == 1:
            zero = jnp.zeros((), jnp.bool_)
            return CollectiveResult(x, zero, zero, 0, 1.0)
        axes = self._composite_axes()
        x = faults.maybe_poison_input(x, axes)
        hplan = plan or self.plan(x.shape, x.dtype)
        from repro.core.collectives import (
            _execute_allreduce_hier, _execute_lossless, _flags_across,
            _nonfinite_local,
        )

        out, ovf = _execute_allreduce_hier(
            x, self.node_axis, self.local_axis, hplan
        )
        overflow, nonfinite = _flags_across(ovf, _nonfinite_local(x), axes)
        degraded = overflow | nonfinite
        fell_back = jnp.zeros((), jnp.bool_)
        if hplan.on_overflow == "fallback":
            # The lossless twin of either branch (flat or hierarchical) is
            # the exact psum over the composite axes.
            cfg = (hplan.flat_plan or hplan.inter).as_config()
            out = lax.cond(
                degraded,
                lambda: _execute_lossless("allreduce", x, axes, cfg),
                lambda: out,
            )
            fell_back = degraded
        elif hplan.on_overflow == "raise":
            io_callback(
                partial(_raise_degraded, f"allreduce over {axes!r}"),
                None, overflow, nonfinite,
            )
        _emit_health("allreduce", axes, overflow, nonfinite, fell_back)
        return CollectiveResult(
            out, overflow, nonfinite,
            hplan.inter_wire_bytes, hplan.ratio,
        )

    def calibrate(self, *, sizes=(1 << 16, 1 << 18, 1 << 20), reps: int = 3,
                  network: Optional[dict] = None,
                  fit_codecs: bool = True) -> "GZHierCommunicator":
        """Codec-fitted (and optionally network-fitted) communicator: like
        ``GZCommunicator.calibrate`` plus per-link-class network terms via
        ``network={'inter': samples, 'intra': samples}`` (measured
        ``(bytes, seconds)`` ppermute timings, e.g. from
        :func:`measure_ppermute` over each axis)."""
        samples_c, samples_d = measure_codec(
            self.config, sizes=sizes, reps=reps
        )
        hw = fit_hardware(samples_c, samples_d, base=self.hw)
        for link, samples in (network or {}).items():
            hw = fit_network(samples, base=hw, link=link)
        if fit_codecs:
            hw = fit_codec_terms(
                measure_codecs(self.config, sizes=sizes, reps=reps), base=hw
            )
        return GZHierCommunicator(
            self.node_axis, self.local_axis, config=self.config,
            policy=self.policy, hw=hw, ratio=self.ratio,
            topology=self._topology, _auto_depth=self._auto_depth,
        )

    def __repr__(self):
        return (
            f"GZHierCommunicator(node={self.node_axis!r}, "
            f"local={self.local_axis!r}, topology={self._topology}, "
            f"policy={self.policy!r}, eb={self.config.eb}, hw={self.hw.name})"
        )


def _communicator_cache(cls, axis_name, config, policy, hw, ratio, axis_size,
                        auto_depth):
    key = (cls, axis_name, config, policy, hw, ratio, axis_size, auto_depth)
    comm = _COMM_CACHE.get(key)
    if comm is None:
        comm = cls(
            axis_name, config=config, policy=policy, hw=hw, ratio=ratio,
            axis_size=axis_size, _auto_depth=auto_depth,
        )
        _COMM_CACHE[key] = comm
    return comm


_COMM_CACHE: dict = {}


# ---------------------------------------------------------------------------
# Calibration: fit cost_model.Hardware from measured codec timings
# ---------------------------------------------------------------------------
#
# t(size) = overhead + size / (peak * util(size)), util(s) = s/(s+sat)
#         = (overhead + sat_bytes/peak) + size/peak            [linear!]
# so a least-squares line through (size, seconds) gives peak = 1/slope and
# overhead = intercept - sat_bytes/peak, with the saturation knee kept
# from the base model (separating knee from overhead needs sub-knee
# resolution that timing noise on small inputs does not give).


def fit_hardware(samples_compress, samples_decompress=None, *,
                 base: cost_model.Hardware = cost_model.TPU_V5E,
                 name: Optional[str] = None) -> cost_model.Hardware:
    """Fit codec throughput/overhead from ``[(size_bytes, seconds), ...]``.

    Returns a new ``Hardware`` with ``cmp_peak_gbps``/``cmp_overhead_us``
    (and ``dec_peak_gbps`` when decompress samples are given) replaced by
    the fitted values; network/reduce terms are inherited from ``base``.
    """
    def _fit(samples):
        pts = np.asarray(sorted(samples), dtype=np.float64)
        if pts.ndim != 2 or pts.shape[0] < 2:
            raise ValueError("need >= 2 (size_bytes, seconds) samples")
        slope, intercept = np.polyfit(pts[:, 0], pts[:, 1], 1)
        peak = 1.0 / max(slope, 1e-18)  # bytes/s
        sat_bytes = base.cmp_saturation_mb * 1e6
        overhead_s = max(intercept - sat_bytes / peak, 0.0)
        return peak * 8 / 1e9, overhead_s * 1e6  # (gbps, us)

    cmp_gbps, cmp_us = _fit(samples_compress)
    kw = dict(cmp_peak_gbps=cmp_gbps, cmp_overhead_us=cmp_us)
    if samples_decompress:
        dec_gbps, _ = _fit(samples_decompress)
        kw["dec_peak_gbps"] = dec_gbps
    return dataclasses.replace(
        base, name=name or f"{base.name}-calibrated", **kw
    )


def fit_network(samples, *, base: cost_model.Hardware,
                link: str = "inter",
                name: Optional[str] = None) -> cost_model.Hardware:
    """Fit one link class's alpha-beta terms from measured hop timings.

    ``samples`` is ``[(bytes_on_wire, seconds), ...]`` from timed
    ``ppermute`` hops over ONE mesh axis (:func:`measure_ppermute`).  The
    model is the cost model's own ``t = alpha + bytes / bw`` — linear in
    bytes, so a least-squares line gives ``bw = 1/slope`` and
    ``alpha = intercept`` directly (the recovery is exact on noiseless
    samples; tests/test_hier.py pins it).

    ``link='inter'`` replaces ``net_gbps``/``net_alpha_us``;
    ``link='intra'`` replaces ``intra_gbps``/``intra_alpha_us`` — fitting
    the intra class on a flat-fabric base thereby DECLARES the fabric
    two-level (``Hardware.intra_terms`` stops inheriting the inter
    terms).
    """
    pts = np.asarray(sorted(samples), dtype=np.float64)
    if pts.ndim != 2 or pts.shape[0] < 2:
        raise ValueError("need >= 2 (bytes, seconds) samples")
    slope, intercept = np.polyfit(pts[:, 0], pts[:, 1], 1)
    bw = 1.0 / max(slope, 1e-18)  # bytes/s
    gbps = bw * 8 / 1e9
    alpha_us = max(intercept, 0.0) * 1e6
    if link == "inter":
        kw = dict(net_gbps=gbps, net_alpha_us=alpha_us)
    elif link == "intra":
        kw = dict(intra_gbps=gbps, intra_alpha_us=alpha_us)
    else:
        raise ValueError(f"unknown link class {link!r}: 'inter' or 'intra'")
    return dataclasses.replace(
        base, name=name or f"{base.name}-net", **kw
    )


def measure_ppermute(mesh, axis_name, *, sizes=(1 << 14, 1 << 17, 1 << 20),
                     reps: int = 3):
    """Time one ring-shift ``ppermute`` hop over ``axis_name`` of ``mesh``
    at each payload size (f32 elements).  Returns ``[(bytes, seconds),
    ...]`` — feed to :func:`fit_network` per link class (the intra-node
    axis times the fast link, the node axis the fabric).  Min-of-reps
    discipline like ``measure_codec``.  On a single-host mesh the numbers
    measure XLA's copy path, not a real fabric — useful for exercising
    the fitting pipeline, not for production calibration.
    """
    import time

    from jax.sharding import PartitionSpec as P

    from repro.core.shmap import shard_map

    sizes_of = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = sizes_of[axis_name]
    perm = schedule.ring_perm(n)

    samples = []
    for n_elems in sizes:
        def body(x):
            return jax.lax.ppermute(x, axis_name, perm)

        fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(),), out_specs=P(),
        ))
        x = jnp.ones((int(n_elems),), jnp.float32)
        jax.block_until_ready(fn(x))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            best = min(best, time.perf_counter() - t0)
        samples.append((int(n_elems) * 4, best))
    return samples


def measure_codec(config=None, *, sizes=(1 << 16, 1 << 18, 1 << 20),
                  reps: int = 3, interpret: Optional[bool] = None):
    """Time compress/decompress at ``sizes`` elements on this host.

    Returns ``(samples_compress, samples_decompress)`` as
    ``[(size_bytes, seconds), ...]`` — feed to :func:`fit_hardware`.  Uses
    the min-of-reps discipline of benchmarks/benchutil.py (noise only ever
    adds time).  ``interpret`` is accepted for symmetry with the kernel
    entry points; the compressor picks its own mode per backend.
    """
    import time

    from repro.core.collectives import GZConfig

    cfg = config if config is not None else GZConfig()
    if cfg.codec == codecs.AUTO:
        # Only a concrete codec can be timed; the default is the dense
        # reference every auto candidate is compared against anyway.
        cfg = dataclasses.replace(cfg, codec="lorenzo")
    comp = cfg.compressor()
    del interpret  # kernels select interpret mode from the backend

    def _time(fn):
        jax.block_until_ready(fn())
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best

    samples_c, samples_d = [], []
    for n in sizes:
        x = jnp.asarray(
            np.cumsum(np.random.default_rng(0).normal(0, 0.01, n)),
            jnp.float32,
        )
        compress = jax.jit(lambda v: comp.compress(v, cfg.eb))
        c = compress(x)
        samples_c.append((n * 4, _time(lambda: compress(x))))
        decompress = jax.jit(comp.decompress)
        samples_d.append((n * 4, _time(lambda: decompress(c))))
    return samples_c, samples_d


def measure_codecs(config=None, *, sizes=(1 << 16, 1 << 18, 1 << 20),
                   reps: int = 3, names=None) -> dict:
    """Time EVERY registered wire codec on this host's smooth sample data.

    Returns ``{codec: {'samples_compress': [(bytes, s), ...],
    'samples_decompress': [...], 'ratio': float}}`` — the input of
    :func:`fit_codec_terms`.  ``ratio`` is the measured payload reduction
    (uncompressed bytes over the TRUE stream bytes, ``payload_bytes``) at
    the largest size — the quantity ``benchmarks/codec_bench.py`` records
    and ``codec='auto'`` ranks with after calibration.  Same smooth-tensor
    and min-of-reps discipline as :func:`measure_codec`.
    """
    from repro.core.collectives import GZConfig

    cfg = config if config is not None else GZConfig()
    measured = {}
    for name in (names if names is not None else codecs.codec_names()):
        cfg_c = dataclasses.replace(cfg, codec=name)
        samples_c, samples_d = measure_codec(cfg_c, sizes=sizes, reps=reps)
        comp = cfg_c.compressor()
        n = max(sizes)
        x = jnp.asarray(
            np.cumsum(np.random.default_rng(0).normal(0, 0.01, n)),
            jnp.float32,
        )
        c = jax.jit(lambda v: comp.compress(v, cfg_c.eb))(x)
        payload = float(jax.device_get(c.payload_bytes()))
        measured[name] = {
            "samples_compress": samples_c,
            "samples_decompress": samples_d,
            "ratio": (n * 4) / max(payload, 1.0),
        }
    return measured


def fit_codec_terms(measured: dict, *,
                    base: cost_model.Hardware,
                    name: Optional[str] = None) -> cost_model.Hardware:
    """Fit per-codec ``CodecTerms`` from :func:`measure_codecs` output.

    Each codec gets its measured compress/decompress throughput (the same
    linear fit as :func:`fit_hardware`) and its measured ratio — recorded
    as a SCALE relative to the dense ``lorenzo`` ratio for eb-scaled
    codecs (their achievable ratio tracks the caller's assumed dense
    ratio across tensor classes) and as an absolute ratio for
    data-intrinsic codecs (lossless/passthrough ship the same bytes
    whatever the bound).  Returns a ``Hardware`` whose ``codec_terms``
    the planner's :func:`_codec_adjusted` resolves ahead of the registry
    defaults.
    """
    def _peak_gbps(samples):
        pts = np.asarray(sorted(samples), dtype=np.float64)
        if pts.ndim != 2 or pts.shape[0] < 2:
            return 0.0
        slope, _ = np.polyfit(pts[:, 0], pts[:, 1], 1)
        return (1.0 / max(slope, 1e-18)) * 8 / 1e9

    dense = measured.get("lorenzo", {}).get("ratio", 1.0)
    terms = []
    for codec in sorted(measured):
        m = measured[codec]
        spec = codecs.get_codec(codec)
        kw = dict(
            cmp_peak_gbps=_peak_gbps(m["samples_compress"]),
            dec_peak_gbps=_peak_gbps(m["samples_decompress"]),
        )
        if spec.eb_scaled:
            kw["ratio_scale"] = m["ratio"] / max(dense, 1e-9)
        else:
            kw["ratio_abs"] = max(m["ratio"], 1.0)
        terms.append(cost_model.CodecTerms(codec, **kw))
    return dataclasses.replace(
        base, codec_terms=tuple(terms),
        name=name or f"{base.name}-codecs",
    )
