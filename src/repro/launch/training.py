"""train_step / serve_step builders — where gZCCL meets the training loop.

The returned step functions are jax.jit-able with explicit in/out
shardings (the dry-run lowers exactly these).  Everything inside is one
shard_map body over the production mesh:

  * forward/backward with FSDP param gathers (optionally gZ-compressed
    allgather; its custom_vjp makes the gradient reduce-scatter compressed
    too — the [29] pattern with gZ error control),
  * the grad-sync rule validated in tests/_mp_model_parallel_child.py:
    psum every grad leaf over each mesh axis ABSENT from its spec; the
    differentiated loss is pre-scaled by 1/(tp * n_dp) to cancel
    shard_map's sum-over-ranks semantics,
  * cross-pod / small-leaf gradient reduction through per-axis
    ``GZCommunicator``s (the paper's headline collective behind the
    plan-then-execute surface of core/comm.py) when a GZConfig is set,
  * AdamW with sharded f32 moments.

Backward-overlapped bucketed sync (ISSUE 9, ``overlap_sync=True``):
instead of one post-hoc ``_sync_grads`` pass after backward completes,
parameter leaves are grouped by sync signature (which mesh axes their
gradient must reduce over), packed last-layer-first into size-targeted
buckets, and each bucket is wrapped in an identity ``custom_vjp`` hook
whose BACKWARD performs that bucket's reduction.  The hook boundary is
where XLA's scheduler sees the collective become ready — as soon as the
bucket's cotangents exist, while the rest of backward is still running —
so comm overlaps compute.  Health flags ride the cotangent of a chained
scalar token (the only dataflow out of a custom_vjp backward is a
cotangent).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import cost_model
from repro.core.collectives import GZConfig
from repro.core.comm import GZCommunicator
from repro.core.grad_sync import SyncConfig
from repro.models.attention import KVCacheSpec
from repro.models.config import ModelConfig
from repro.models.model import Model
from repro.models.parallel import ParallelCtx, param_specs, param_shapes
from repro.core.shmap import shard_map
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

__all__ = ["TrainSetup", "make_setup", "make_train_step", "make_serve_step"]

# Named scopes of the train step's own layers (op metadata in the compiled
# HLO, read per layer from a device profile); the model's are in
# models/model.py.
GRAD_SYNC_SCOPE = "train.grad_sync"  # gradient reduction over the dp axes
OPTIMIZER_SCOPE = "train.optimizer"  # global norm, AdamW, skip merge


@dataclasses.dataclass(frozen=True)
class TrainSetup:
    cfg: ModelConfig
    ctx: ParallelCtx
    model: Model
    mesh: object
    defs: dict
    specs: dict
    opt: AdamWConfig
    grad_gz: Optional[GZConfig]  # gz knobs for the dp-axis grad allreduce
    # resolve-once communicators, one per data-parallel axis, bound to the
    # mesh axis sizes at setup time (plan resolution is a cache hit inside
    # the traced step body) — empty when gradient sync is plain psum
    grad_comms: tuple = ()
    # GradScaler-style degraded-step skip: when True, a train step whose
    # gradient sync reports overflow or non-finite input keeps the OLD
    # params/opt state (jnp.where merge, donation-safe) and flags it in
    # metrics["skipped"] instead of applying a corrupted update.  Mostly
    # useful with on_overflow="flag"; with "fallback" the values are
    # already exact and steps are never skipped for overflow alone.
    skip_on_overflow: bool = False
    # ISSUE 9 bucketed-overlap knobs: sync each gradient bucket from a
    # custom_vjp hook inside backward (instead of one post-hoc pass)...
    overlap_sync: bool = False
    # ...packing whole leaves last-layer-first into buckets of about this
    # many f32 bytes (0 never reaches here: make_setup resolves auto to
    # the BucketPlan's choice).
    bucket_bytes: int = 16 * 1024 * 1024

    def opt_specs(self):
        return {
            "mu": self.specs,
            "nu": self.specs,
            "step": P(),
        }

    def named(self, spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )


def _strip_axis(spec: P, ax: str) -> P:
    def strip(entry):
        if entry == ax:
            return None
        if isinstance(entry, tuple):
            kept = tuple(e for e in entry if e != ax)
            return kept if kept else None
        return entry

    return P(*(strip(e) for e in tuple(spec)))


def _tree_param_count(defs) -> int:
    total = 0
    for s in jax.tree.leaves(param_shapes(defs)):
        size = 1
        for d in s.shape:
            size *= int(d)
        total += size
    return total


def make_setup(
    cfg: ModelConfig,
    mesh,
    *,
    opt: AdamWConfig = AdamWConfig(),
    fsdp_gz: Optional[GZConfig] = None,
    grad_gz: Optional[GZConfig] = None,
    grad_policy: str = "auto",
    remat: str = "full",
    fsdp: bool = True,
    skip_on_overflow: bool = False,
    overlap_sync: bool = False,
    bucket_bytes: int = 0,
    overlap_tokens: int = 4096,
    overlap_hw: Optional[cost_model.Hardware] = None,
) -> TrainSetup:
    """``fsdp=False`` replicates parameters over the data axis (no per-layer
    gathers) — the weights-resident serving mode (§Perf hillclimb 1).

    ``grad_policy`` names the communicator plan policy ("auto" | "paper" |
    "throughput" | "accuracy" — core/comm.py) used when ``grad_gz`` leaves
    the algorithm choice open.

    ``overlap_sync`` turns on the per-bucket backward hooks;
    ``bucket_bytes == 0`` asks ``cost_model.best_bucket_plan`` to co-plan
    the bucket size with the ring pipeline depth at ``overlap_hw``
    (default the calibrated A100/Slingshot point) for a step of
    ``overlap_tokens`` tokens; > 0 forces the size.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_axes = tuple(ax for ax in mesh.axis_names if ax in ("pod", "data"))
    grad_comms = ()
    if grad_gz is not None:
        grad_comms = tuple(
            (ax, GZCommunicator.for_config(
                ax, grad_gz, policy=grad_policy, axis_size=sizes.get(ax, 1)))
            for ax in dp_axes
        )
    fsdp_sync = None
    if fsdp_gz:
        # mark_degraded rides skip_on_overflow: with a skip handler the
        # NaN-marked cotangent of a degraded sharded-axis reduce-scatter
        # is caught by _sync_grads' per-leaf probe; without one a NaN
        # step would be worse than a flagged lossy one.
        fsdp_sync = SyncConfig(gz=fsdp_gz, relative_eb=False,
                               mark_degraded=skip_on_overflow)
    ctx = ParallelCtx(
        tp_axis="model",
        fsdp_axis="data",
        dp_axes=dp_axes,
        tp_size=sizes.get("model", 1),
        fsdp_size=sizes.get("data", 1) if fsdp else 1,
        fsdp_sync=fsdp_sync,
        remat=remat,
    )
    model = Model(cfg, ctx)
    defs = model.param_defs()
    if not fsdp:
        defs = jax.tree.map(
            lambda d: dataclasses.replace(d, spec=_strip_axis(d.spec, "data")),
            defs,
            is_leaf=lambda x: hasattr(x, "spec") and hasattr(x, "init"),
        )
    n_dp = 1
    for ax in dp_axes:
        n_dp *= sizes.get(ax, 1)
    overlap_plan = None
    if grad_gz is not None and n_dp > 1:
        n_params = _tree_param_count(defs)
        overlap_plan = cost_model.best_bucket_plan(
            overlap_hw or cost_model.A100_SLINGSHOT,
            tree_bytes=4.0 * n_params,
            backward_flops=4.0 * n_params * overlap_tokens,
            n=n_dp,
        )
    if bucket_bytes <= 0:
        bucket_bytes = (overlap_plan.bucket_bytes if overlap_plan
                        else SyncConfig().bucket_bytes)
    return TrainSetup(
        cfg=cfg, ctx=ctx, model=model, mesh=mesh, defs=defs,
        specs=param_specs(defs), opt=opt, grad_gz=grad_gz,
        grad_comms=grad_comms, skip_on_overflow=skip_on_overflow,
        overlap_sync=overlap_sync, bucket_bytes=bucket_bytes,
    )


def _axes_in_spec(spec: P) -> set:
    return set(jax.tree.leaves(tuple(spec)))


def _sync_grads(grads, specs, mesh_axes, grad_comms: dict):
    """psum each leaf over every mesh axis absent from its spec.

    Reductions over dp axes with a bound communicator go through the
    compressed ``comm.allreduce`` (plan pre-resolved at setup time); the
    tiny "model"-axis cases stay psum.  Returns ``(grads, degraded)``
    where ``degraded`` ORs every leaf's health bit.

    EVERY leaf contributes a bit, not only the ones routed through a dp
    communicator (the ISSUE 9 satellite): a leaf sharded over the fsdp
    axis arrives here already reduce-scattered by ``fsdp_all_gather``'s
    backward — its overflow rides in as a NaN mark
    (``SyncConfig.mark_degraded``), and the per-leaf nonfinite probe
    below is what delivers it (and any plain non-finite gradient on a
    psum-only path) to ``skip_on_overflow``.
    """
    # A mutable cell: jax.tree.map's per-leaf callback can't return two
    # things without restructuring every caller, so the health bit
    # accumulates on the side (trace-safe — it's just op building).
    flag = [jnp.zeros((), jnp.bool_)]

    def sync(g, s):
        present = _axes_in_spec(s)
        flag[0] = flag[0] | jnp.any(~jnp.isfinite(g))
        for ax in mesh_axes:
            if ax in present:
                continue
            comm = grad_comms.get(ax)
            if comm is not None:
                res = comm.allreduce(g)
                g = res.value
                flag[0] = flag[0] | res.overflow | res.nonfinite
            else:
                g = lax.psum(g, ax)
        return g

    with jax.named_scope(GRAD_SYNC_SCOPE):
        out = jax.tree.map(sync, grads, specs)
        return out, flag[0]


# ---------------------------------------------------------------------------
# Backward-overlapped bucketed sync (ISSUE 9)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _BucketMeta:
    """Static description of one bucket hook (hashable: custom_vjp keys
    its nondiff args).  ``ops`` is the leaves' shared sync signature —
    ((axis, communicator-or-None), ...) over the mesh axes ABSENT from
    their specs, in mesh order, exactly the reduction _sync_grads would
    have applied post-hoc."""

    ops: tuple
    shapes: tuple
    dtypes: tuple


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bucket_hook(meta: _BucketMeta, leaves, token):
    """Identity on ``(leaves, token)``; the custom_vjp BACKWARD performs
    this bucket's gradient reduction the moment its cotangents exist, so
    XLA can overlap the collective with the rest of backward.  The health
    flag leaves the backward as the token's cotangent (the only dataflow
    channel out), chained across hooks so grad-of-token accumulates every
    bucket's bit."""
    return leaves, token


def _bucket_hook_fwd(meta, leaves, token):
    return (leaves, token), None


def _bucket_hook_bwd(meta, _res, ct):
    gs, g_token = ct
    with jax.named_scope(GRAD_SYNC_SCOPE):
        flat = [g.astype(jnp.float32).reshape(-1) for g in gs]
        vec = flat[0] if len(flat) == 1 else jnp.concatenate(flat)
        # Per-leaf nonfinite probe (the _sync_grads satellite, hook
        # edition): catches NaN-marked fsdp reduce-scatter cotangents even
        # when this bucket needs no collective of its own.
        flag = jnp.any(~jnp.isfinite(vec))
        for ax, comm in meta.ops:
            if comm is None:
                vec = lax.psum(vec, ax)
            else:
                res = comm.allreduce(vec)
                vec = res.value
                flag = flag | res.overflow | res.nonfinite
        outs, off = [], 0
        for shape, dt in zip(meta.shapes, meta.dtypes):
            size = 1
            for d in shape:
                size *= int(d)
            outs.append(vec[off:off + size].reshape(shape).astype(dt))
            off += size
        return tuple(outs), g_token + flag.astype(g_token.dtype)


_bucket_hook.defvjp(_bucket_hook_fwd, _bucket_hook_bwd)


def _install_bucket_hooks(params, specs, mesh_axes, grad_comms: dict,
                          bucket_bytes: int, token):
    """Wrap every param leaf in a per-bucket sync hook.

    Leaves are grouped by sync signature (identical reduction sequence —
    a bucket's concatenated payload must mean ONE collective), then
    packed greedily into ~``bucket_bytes`` f32 buckets walking the
    flatten order BACKWARD: the tree's tail (loss-side parameters) gets
    the first buckets, matching the order backward completes cotangents.
    Returns ``(hooked_params, token_out, n_buckets)``.
    """
    leaves, treedef = jax.tree.flatten(params)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    groups: dict = {}
    for i, spec in enumerate(spec_leaves):
        present = _axes_in_spec(spec)
        ops = tuple((ax, grad_comms.get(ax)) for ax in mesh_axes
                    if ax not in present)
        groups.setdefault(ops, []).append(i)
    new_leaves = list(leaves)
    n_buckets = 0
    for ops, idxs in groups.items():
        bucket: list = []
        pending = 0
        for i in reversed(idxs):  # last-layer-first
            bucket.append(i)
            pending += int(leaves[i].size) * 4
            if pending < bucket_bytes and i != idxs[0]:
                continue
            meta = _BucketMeta(
                ops=ops,
                shapes=tuple(leaves[j].shape for j in bucket),
                dtypes=tuple(str(leaves[j].dtype) for j in bucket),
            )
            outs, token = _bucket_hook(
                meta, tuple(new_leaves[j] for j in bucket), token
            )
            for j, o in zip(bucket, outs):
                new_leaves[j] = o
            n_buckets += 1
            bucket, pending = [], 0
    return jax.tree.unflatten(treedef, new_leaves), token, n_buckets


def _skip_merge(degraded, new_tree, old_tree):
    """Keep ``old_tree`` wherever this step degraded (replicated bool
    scalar predicate), else take ``new_tree`` — the GradScaler-style skip.
    Elementwise ``jnp.where`` (not lax.cond) so both sides stay donatable
    and the merge vectorizes into the update itself."""
    return jax.tree.map(
        lambda new, old: jnp.where(degraded, old, new), new_tree, old_tree
    )


def _global_grad_norm(grads, specs, sizes) -> jnp.ndarray:
    """Exact global norm of the synced (logical) gradient: local sum of
    squares per leaf / replication factor, psum'd over the whole mesh."""
    total = jnp.float32(0.0)
    mesh_axes = list(sizes)
    for g, s in zip(jax.tree.leaves(grads), jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))):
        present = _axes_in_spec(s)
        rep = 1
        for ax in mesh_axes:
            if ax not in present:
                rep *= sizes[ax]
        total = total + jnp.sum(jnp.square(g.astype(jnp.float32))) / rep
    for ax in mesh_axes:
        total = lax.psum(total, ax)
    return jnp.sqrt(total)


def make_train_step(setup: TrainSetup, batch_specs):
    """Returns step(params, opt_state, batch) -> (params, opt_state, metrics)."""
    cfg, ctx, model = setup.cfg, setup.ctx, setup.model
    sizes = dict(zip(setup.mesh.axis_names, setup.mesh.devices.shape))
    mesh_axes = tuple(setup.mesh.axis_names)
    n_dp = 1
    for ax in ctx.dp_axes:
        n_dp *= sizes[ax]
    scale = 1.0 / (ctx.tp_size * n_dp)
    specs = setup.specs
    grad_comms = dict(setup.grad_comms)

    def body(params, opt_state, batch):
        if setup.overlap_sync:
            token0 = jnp.zeros((), jnp.float32)

            def scaled_loss(p, tok):
                p, tok_out, _ = _install_bucket_hooks(
                    p, specs, mesh_axes, grad_comms,
                    setup.bucket_bytes, tok,
                )
                # 0.0 * tok_out gives the token chain a real cotangent
                # edge without perturbing the loss: every hook backward
                # then adds its bucket's health bit to grad-of-token.
                return model.loss_fn(p, batch) * scale + 0.0 * tok_out

            loss, (grads, g_token) = jax.value_and_grad(
                scaled_loss, argnums=(0, 1)
            )(params, token0)
            degraded = g_token > 0
        else:
            def scaled_loss(p):
                return model.loss_fn(p, batch) * scale

            loss, grads = jax.value_and_grad(scaled_loss)(params)
            grads, degraded = _sync_grads(
                grads, specs, mesh_axes, grad_comms
            )
        loss = loss / scale
        for ax in ctx.dp_axes:
            loss = lax.pmean(loss, ax)
        # Each health bit is replicated over its OWN dp axis only; make
        # the skip predicate globally consistent before it gates state.
        degraded = lax.psum(degraded.astype(jnp.int32), mesh_axes) > 0
        with jax.named_scope(OPTIMIZER_SCOPE):
            gnorm = _global_grad_norm(grads, specs, sizes)
            new_params, new_opt, om = adamw_update(
                params, grads, opt_state, setup.opt, grad_norm=gnorm
            )
            skipped = jnp.zeros((), jnp.bool_)
            if setup.skip_on_overflow:
                new_params = _skip_merge(degraded, new_params, params)
                new_opt = _skip_merge(degraded, new_opt, opt_state)
                skipped = degraded
        metrics = {
            "loss": loss, "gnorm": om["gnorm"], "lr": om["lr"],
            "skipped": skipped,
        }
        return new_params, new_opt, metrics

    ospecs = setup.opt_specs()
    mspecs = {"loss": P(), "gnorm": P(), "lr": P(), "skipped": P()}
    step = shard_map(
        body,
        mesh=setup.mesh,
        in_specs=(specs, ospecs, batch_specs),
        out_specs=(specs, ospecs, mspecs),
    )
    return jax.jit(
        step,
        in_shardings=(setup.named(specs), setup.named(ospecs),
                      setup.named(batch_specs)),
        out_shardings=(setup.named(specs), setup.named(ospecs),
                       setup.named(mspecs)),
        donate_argnums=(0, 1),
    )


def make_serve_step(setup: TrainSetup, cache_specs, tokens_spec, plan: KVCacheSpec):
    """Returns step(params, cache, tokens, pos) -> (logits, new_cache)."""
    model = setup.model
    specs = setup.specs
    v = setup.cfg.padded_vocab()

    def body(params, cache, tokens, pos):
        logits, new_cache = model.decode_fn(params, cache, tokens, pos[0], plan)
        return logits, new_cache

    logits_spec = P(*(tuple(tokens_spec)[:1] + (None, None)))
    step = shard_map(
        body,
        mesh=setup.mesh,
        in_specs=(specs, cache_specs, tokens_spec, P(None)),
        out_specs=(logits_spec, cache_specs),
    )
    return jax.jit(
        step,
        in_shardings=(
            setup.named(specs),
            setup.named(cache_specs),
            NamedSharding(setup.mesh, tokens_spec),
            NamedSharding(setup.mesh, P(None)),
        ),
        donate_argnums=(1,),
    )
