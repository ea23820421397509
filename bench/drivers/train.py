"""Data-parallel training steps of a model: the program's jitted
``make_train_step``, one step in flight, a new batch every step.

Set-up builds the one compiled step with its state (the bench's seeded
weights, in the program's shardings) and drives it through the
configuration's ``check_steps`` first steps by the window's own call and
feed; the window then goes on from there. After the window the plain
reference trains the same weights on the same rows for those steps, and
the driver compares each step's loss, the per-leaf norms of the first
gradient as the optimizer got it (read from its first moment after one
step) and the per-leaf norms of the parameters' change after the check
steps.

Traffic keys: ``seq`` and ``global_batch`` (sharded over the data axis).
"""
from __future__ import annotations

import statistics
import time

from bench import data, harness, trace


def relative_gaps(prog: dict, ref: dict, keep) -> dict:
    """Per leaf |prog - ref| / max(ref, median leaf of ref), for ``keep``."""
    med = statistics.median(ref.values())
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def check_batches(ctx: harness.Context, half: bool = False) -> list:
    """The rows of the check steps, drawn from the seed as the window's
    feed draws them. ``half`` repeats each batch's first half of rows in
    place of the second (the fault of a step that leaves half of its
    batch out)."""
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    gb, seq = int(tr["global_batch"]), int(tr["seq"])
    stream = data.TokenStream(cfg["vocab_size"], gb, seq, ctx.seed)
    batches = [next(stream) for _ in range(int(cfg["check_steps"]))]
    if half:
        for b in batches:
            for v in b.values():
                v[gb // 2:] = v[:gb // 2]
    return batches


def reference(ctx: harness.Context, ref, compute: str, half: bool = False):
    """The plain reference over the check steps' rows: (losses, per-leaf
    first clipped gradient norms, per-leaf change norms)."""
    return ref.train(ctx.cell.config, data.key(ctx.seed),
                     check_batches(ctx, half), compute=compute)


def compare(prog, ref, limits: dict, say) -> list:
    """The numbers compared, as checks against ``limits``. ``prog`` and
    ``ref`` are (losses, first gradient norms, change norms). Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out. Beside the worst leaf's gradient gap,
    which one sensitive leaf can hold on some seeds, the median leaf's is
    compared: lower-precision arithmetic moves every leaf."""
    losses, first, change = prog
    ref_losses, ref_first, ref_change = ref
    med = statistics.median(ref_first.values())
    keep = [k for k, v in ref_first.items() if v >= 1e-3 * med]
    loss_gap = max(abs(a - b) for a, b in zip(losses, ref_losses))
    g = relative_gaps(first, ref_first, keep)
    c = relative_gaps(change, ref_change, keep)
    worst_g = max(g, key=g.get)
    worst_c = max(c, key=c.get)
    say(f"train: losses program={losses} reference={ref_losses}")
    say(f"train: leaves compared {len(keep)} of {len(ref_first)}; left "
        f"out {sorted(set(ref_first) - set(keep))}")
    say(f"train: first gradient worst leaf {worst_g} program="
        f"{first[worst_g]:.6g} reference={ref_first[worst_g]:.6g}")
    say(f"train: change worst leaf {worst_c} program="
        f"{change[worst_c]:.6g} reference={ref_change[worst_c]:.6g}")
    say("train: first gradient gaps by leaf " + " ".join(
        f"{k}={v:.4g}" for k, v in sorted(g.items())))
    return [harness.Check("loss_gap", loss_gap, float(limits["loss_gap"])),
            harness.Check("first_grad_norm_gap", g[worst_g],
                          float(limits["first_grad_norm_gap"])),
            harness.Check("first_grad_median_gap", statistics.median(g.values()),
                          float(limits["first_grad_median_gap"])),
            harness.Check("update_norm_gap", c[worst_c],
                          float(limits["update_norm_gap"]))]


def control(ctx: harness.Context) -> list:
    """The reference computed in fp8 put in the program's place."""
    ref = harness.reference_module(ctx.cell, ctx.root)
    want = reference(ctx, ref, "f32")
    got = reference(ctx, ref, "fp8")
    return compare(got, want, ctx.cell.config["limits"], ctx.say)


def faults(ctx: harness.Context) -> dict:
    """Readings of the faults a one-chip training cell can have, planted in
    the reference put in the program's place: half of the batch left out
    (the mean over the rest), and a step that returns its state unchanged
    (every loss that of the first weights, no gradient reaches the
    optimizer, nothing moves)."""
    ref = harness.reference_module(ctx.cell, ctx.root)
    cfg = ctx.cell.config
    want = reference(ctx, ref, "f32")
    half = reference(ctx, ref, "f32", half=True)
    zeros = {k: 0.0 for k in want[1]}
    frozen = (ref.init_losses(cfg, data.key(ctx.seed), check_batches(ctx)),
              zeros, zeros)
    return {name: compare(got, want, cfg["limits"], ctx.say)
            for name, got in (("half_batch", half),
                              ("state_unchanged", frozen))}


class Driver:
    def __init__(self, ctx: harness.Context):
        import jax

        from repro.configs import registry
        from repro.core.collectives import GZConfig
        from repro.launch.shapes import InputShape, train_specs
        from repro.launch.training import make_setup
        from repro.models.parallel import param_shapes
        from repro.optim.adamw import AdamWConfig, adamw_init

        self.ctx = ctx
        cfg, tr = ctx.cell.config, ctx.cell.traffic
        self.cfg = cfg
        self.ref = harness.reference_module(ctx.cell, ctx.root)
        self.setup_parts = {}
        prog = cfg["program"]
        mcfg = registry.get(prog["arch"], smoke=prog["smoke"])
        bad = self.ref.check_model(cfg, mcfg)
        if bad:
            raise harness.BenchError(
                f"program model differs from the configuration: {bad}")
        n = len(ctx.devices)
        self.global_batch, self.seq = int(tr["global_batch"]), int(tr["seq"])
        self.tokens_per_step = self.global_batch * self.seq
        mesh = jax.make_mesh((n, 1), ("data", "model"), devices=ctx.devices)
        setup = make_setup(
            mcfg, mesh, opt=AdamWConfig(**cfg["optimizer"]),
            grad_gz=GZConfig(eb=cfg["grad_gz"]["eb"]),
            grad_policy=prog["grad_policy"], remat=prog["remat"],
            fsdp=prog["fsdp"])
        _, bspecs = train_specs(
            mcfg, InputShape("bench", self.seq, self.global_batch, "train"),
            mesh)
        self.batch_sharding = setup.named(bspecs)
        self.key = data.key(ctx.seed)
        init = jax.jit(lambda k: self.ref.init_params(cfg, k),
                       out_shardings=setup.named(setup.specs))
        want = jax.tree.map(lambda s: (s.shape, s.dtype),
                            param_shapes(setup.defs))
        got = jax.tree.map(lambda s: (s.shape, s.dtype),
                           jax.eval_shape(init, self.key))
        if want != got:
            raise harness.BenchError(f"bench weights {got} do not match the "
                                     f"program's parameters {want}")

        t = time.perf_counter()
        self.stream = data.TokenStream(cfg["vocab_size"], self.global_batch,
                                       self.seq, ctx.seed)
        self.pending = self._next_batch()
        self.params = init(self.key)
        self.opt = jax.jit(adamw_init, out_shardings=setup.named(
            setup.opt_specs()))(self.params)
        jax.block_until_ready((self.params, self.opt))
        self.setup_parts["init"] = time.perf_counter() - t

        t = time.perf_counter()
        self.step = self._build_step(setup, bspecs).lower(
            self.params, self.opt, self.pending).compile()
        self._cats = trace.categories_from_hlo(self.step.as_text())
        b1 = cfg["optimizer"]["b1"]
        self._mu_norms = jax.jit(lambda mu: {
            k: v / (1 - b1) for k, v in self.ref._norms(mu).items()})
        self._change_norms = self.ref.change_norms(cfg)
        self.setup_parts["compile"] = time.perf_counter() - t

        t = time.perf_counter()
        self.losses, self.first_grad, self.change = [], None, None
        for i in range(int(cfg["check_steps"])):
            self.call(i)
            self.losses.append(float(self.metrics["loss"]))
            if i == 0:
                self.first_grad = {k: float(v) for k, v in
                                   self._mu_norms(self.opt["mu"]).items()}
        self.change = {k: float(v) for k, v in
                       self._change_norms(self.params, self.key).items()}
        self.setup_parts["check_steps"] = time.perf_counter() - t

    def _build_step(self, setup, bspecs):
        from repro.launch.training import make_train_step

        return make_train_step(setup, bspecs)

    def _next_batch(self):
        import jax

        b = next(self.stream)
        return jax.device_put(b, self.batch_sharding)

    def call(self, i: int) -> None:
        import jax

        with harness.annotate("data"):
            batch = self.pending if self.pending is not None else \
                self._next_batch()
            self.pending = None
        with harness.annotate("dispatch"):
            self.params, self.opt, self.metrics = self.step(
                self.params, self.opt, batch)
        with harness.annotate("block"):
            jax.block_until_ready(self.metrics)

    def op_categories(self) -> dict:
        return self._cats

    def end_to_end(self, latencies, window_s: float) -> dict:
        return {"train_tokens_per_s":
                len(latencies) * self.tokens_per_step / window_s}

    def counters(self) -> dict:
        return {"tokens_per_step": self.tokens_per_step,
                "flops_per_token": self.ref.flops_per_token(self.cfg)}

    def _reference(self, compute: str):
        return reference(self.ctx, self.ref, compute)

    def finish(self) -> harness.Outcome:
        import gc

        del self.params, self.opt, self.step, self.metrics
        self.pending = None
        gc.collect()
        t = time.perf_counter()
        checks = compare((self.losses, self.first_grad, self.change),
                         self._reference("f32"), self.cfg["limits"],
                         self.ctx.say)
        self.ctx.say(f"train: reference took {time.perf_counter() - t:.3f}s")
        return harness.Outcome(checks=checks,
                               failed=sum(not c.ok for c in checks))
