"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch minitron-8b --smoke \
        --steps 50 --batch 8 --seq 128 [--grad-gz redoub] [--eb 1e-4]

On a CPU host it trains the reduced (smoke) configs — a few hundred
steps of a ~100M-class model is examples/quickstart.py.  On TPU chips the
same driver runs the full configs over a (data, model) mesh of the local
devices; ``chip_smoke.py`` runs mamba2-780m at full width this way.

Parameters and optimizer state are created directly in their shardings,
and the step is compiled ahead of time so its device memory
(``memory_analysis``) is printed before the first step runs.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint
from repro.configs import registry
from repro.core.collectives import GZConfig
from repro.data.pipeline import SyntheticStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.shapes import InputShape, train_specs
from repro.launch.training import make_setup, make_train_step
from repro.models.parallel import init_params
from repro.optim.adamw import AdamWConfig, adamw_init


def train(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-8b", choices=registry.arch_ids())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-gz", default=None,
                    choices=["auto", "redoub", "ring", "intring"])
    ap.add_argument("--policy", default="auto",
                    choices=["auto", "paper", "throughput", "accuracy"],
                    help="communicator plan policy when --grad-gz leaves "
                         "the algorithm open (core/comm.py)")
    ap.add_argument("--eb", type=float, default=1e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = registry.get(args.arch, smoke=args.smoke)
    n_dev = len(jax.devices())
    # widest (data, model) factorization available on this host
    data = 1
    while data * 2 <= n_dev and args.batch % (data * 2) == 0 and (n_dev // (data * 2)) * (data * 2) == n_dev:
        data *= 2
    model_par = 1
    mesh = jax.make_mesh((data, model_par), ("data", "model"))

    gz = GZConfig(eb=args.eb, algo=args.grad_gz) if args.grad_gz else None
    opt = AdamWConfig(lr=args.lr, total_steps=args.steps,
                      warmup_steps=max(args.steps // 20, 1))
    setup = make_setup(cfg, mesh, opt=opt, grad_gz=gz, grad_policy=args.policy)
    shape = InputShape("cli", args.seq, args.batch, "train")
    _, bspecs = train_specs(cfg, shape, mesh)
    step_fn = make_train_step(setup, bspecs)

    init_fn = jax.jit(lambda key: init_params(setup.defs, key),
                      out_shardings=setup.named(setup.specs))
    opt_init_fn = jax.jit(adamw_init,
                          out_shardings=setup.named(setup.opt_specs()))
    key = jax.random.key(args.seed)
    stream = SyntheticStream(cfg, args.batch, args.seq, seed=args.seed)

    print(f"arch={cfg.arch_id} params={cfg.param_count()/1e6:.1f}M "
          f"layers={cfg.n_layers} d_model={cfg.d_model} vocab={cfg.vocab} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"grad_gz={args.grad_gz}")
    # Compile from shapes first: the step's device memory is known before
    # any parameter is allocated.
    pshapes = jax.eval_shape(init_fn, key)
    batch = next(stream)
    t0 = time.time()
    step_fn = step_fn.lower(
        pshapes, jax.eval_shape(opt_init_fn, pshapes), batch).compile()
    mem = step_fn.memory_analysis()
    gib = lambda b: f"{b / 2**30:.2f}GiB"
    print(f"train step compiled in {time.time() - t0:.1f}s; per-device "
          f"memory: args {gib(mem.argument_size_in_bytes)} "
          f"temp {gib(mem.temp_size_in_bytes)} "
          f"out {gib(mem.output_size_in_bytes)} "
          f"(aliased {gib(mem.alias_size_in_bytes)})")
    params = init_fn(key)
    opt_state = opt_init_fn(params)
    losses = []
    t0 = time.time()
    for step in range(args.steps):
        if step:
            batch = next(stream)
        params, opt_state, m = step_fn(params, opt_state, batch)
        loss = float(m["loss"])
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            print(f"step {step:5d} loss {loss:.4f} gnorm {float(m['gnorm']):.3f} "
                  f"lr {float(m['lr']):.2e} ({dt:.1f}s)")
        if args.ckpt_dir and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            d = checkpoint.save(args.ckpt_dir, step + 1,
                                {"params": params, "opt": opt_state})
            print(f"  ckpt -> {d}")
    assert np.isfinite(losses).all(), "NaN loss"
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    return losses


if __name__ == "__main__":
    train()
