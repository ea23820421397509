"""Serving driver: batched greedy decode with a KV/state cache.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --smoke \
        --batch 4 --prompt-len 16 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.core.shmap import shard_map
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.training import make_setup
from repro.models.attention import KVCacheSpec
from repro.models.parallel import init_params, param_specs


def serve(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-780m", choices=registry.arch_ids())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = registry.get(args.arch, smoke=args.smoke)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    setup = make_setup(cfg, mesh)
    model = setup.model
    plan = KVCacheSpec(s_total=args.cache_len, cp_axis=None, cp_size=1)
    shapes = model.cache_defs(args.batch, plan)
    rng = np.random.default_rng(args.seed)
    cache = {k: jnp.zeros(v, jnp.float32) for k, v in shapes.items()}
    if "enc_out" in cache:
        cache["enc_out"] = jnp.asarray(
            rng.normal(0, 1, shapes["enc_out"]).astype(np.float32))

    specs = setup.specs
    cspecs = {k: P(*((None,) * len(v))) for k, v in shapes.items()}

    def body(p, c, t, pos):
        logits, nc = model.decode_fn(p, c, t, pos[0], plan)
        return logits, nc

    step = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(specs, cspecs, P(None, None), P(None)),
        out_specs=(P(None, None, None), cspecs),
    ))

    params = init_params(setup.defs, jax.random.key(args.seed))
    prompt = rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)).astype(
        np.int32)

    # prefill token-by-token (decode-path prefill keeps one code path)
    t0 = time.time()
    tok = None
    out_tokens = []
    for i in range(args.prompt_len + args.gen):
        if i < args.prompt_len:
            tok = jnp.asarray(prompt[:, i : i + 1])
        logits, cache = step(params, cache, tok, jnp.asarray([i]))
        nxt = jnp.argmax(logits[:, :, : cfg.vocab], axis=-1).astype(jnp.int32)
        if i >= args.prompt_len - 1:
            tok = nxt
            out_tokens.append(np.asarray(nxt)[:, 0])
    dt = time.time() - t0
    gen = np.stack(out_tokens, axis=1)
    n_tok = args.batch * (args.prompt_len + args.gen)
    print(f"arch={cfg.arch_id} decoded {gen.shape[1]} tokens x{args.batch} "
          f"in {dt:.2f}s ({n_tok/dt:.1f} tok/s incl. prefill)")
    print("sample:", gen[0][:16])
    assert np.isfinite(np.asarray(logits)).all()
    return gen


if __name__ == "__main__":
    serve()
