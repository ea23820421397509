"""Plain reference of mamba2-780m data-parallel training, and the bench's
seeded weights.

The forward pass follows the Mamba-2 paper (arXiv:2405.21060): per layer
an RMSNorm, the input projections (z, x, B and C with one group, dt), a
depthwise causal convolution of width ``d_conv`` over (x, B, C) with SiLU,
the SSD recurrence y_t = sum_{s<=t} (C_t . B_s) exp(A sum_{r=s+1..t} dt_r)
dt_s x_s + D x_t, computed by the paper's chunked "minimal SSD" listing,
the SiLU(z) gate with a gated RMSNorm, the output projection and the
residual add; then a final RMSNorm, the LM head over the padded vocabulary
and the mean cross-entropy. AdamW with global-norm clipping and the cosine
schedule of the configuration's ``optimizer`` follows.

Departures from the published model, as the configuration states them
(its ``departs`` key): untied input and output embeddings, a vocabulary
of 50,280 padded to a multiple of 512 (the padded logits take part in the
softmax, as in the published model's padded head), a bf16 residual
stream, no convolution bias (nor projection biases, as published).

Arithmetic is float32 at ``highest`` matmul precision; parameters are
held in the configuration's types (bfloat16, with ``A_log``, ``D`` and
``dt_bias`` in float32), and gradients in the type of their parameter.
``compute="fp8"`` is the control, computed as fp8 training does: every
matmul operand of the forward pass is rounded to float8 e4m3 and every
incoming gradient of the backward pass to float8 e5m2, each tensor with a
scale of its own that maps its largest magnitude to the type's largest
value; products accumulate in float32.

``check_model`` and ``flops_per_token`` are what the train driver and the
``mfu.train`` reader need of this architecture.

Nothing here imports the program. ``init_params`` is the bench's own
weight generator: the train driver gives the program the same weights,
made from the seed by this function.
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
from jax import lax


def padded_vocab(cfg) -> int:
    m = cfg["pad_vocab_size_multiple"]
    return -(-cfg["vocab_size"] // m) * m


def check_model(cfg, mcfg) -> list:
    """The keys in which the program's model config ``mcfg`` differs from
    this configuration, shape for shape: (key, configuration, program)."""
    s = mcfg.ssm
    pairs = {
        "d_model": mcfg.d_model, "n_layer": mcfg.n_layers,
        "vocab_size": mcfg.vocab, "d_state": s.d_state,
        "headdim": s.head_dim, "expand": s.expand, "chunk_size": s.chunk,
        "d_conv": s.conv_width, "norm_eps": mcfg.norm_eps,
        "dtype": mcfg.dtype,
    }
    bad = [(k, cfg[k], v) for k, v in pairs.items() if cfg[k] != v]
    if padded_vocab(cfg) != mcfg.padded_vocab():
        bad.append(("padded_vocab", padded_vocab(cfg), mcfg.padded_vocab()))
    return bad


def forward_flops_per_token(cfg) -> float:
    """Forward FLOPs per token, 2 per multiply-add, from the shapes.

    Per layer: the input projections (z, x, B and C with one group, dt),
    the depthwise causal convolution over (x, B, C), the SSD in its
    chunked form (within a chunk of Q: C.B over the causal half of the
    chunk, shared by the heads, and its product with x; across chunks: the
    chunk state B.x and its read-out C.state) and the output projection.
    Then the LM head over the model's vocabulary. Norms, gates and
    elementwise work are not counted.
    """
    d = cfg["d_model"]
    di = cfg["expand"] * d
    n = cfg["d_state"]
    p = cfg["headdim"]
    h = di // p
    q = cfg["chunk_size"]
    w = cfg["d_conv"]
    v = cfg["vocab_size"]
    proj_in = 2 * d * (2 * di + 2 * n + h)
    conv = 2 * w * (di + 2 * n)
    ssd_diag = 2 * n * (q / 2) + 2 * h * p * (q / 2)
    ssd_state = 2 * (2 * h * p * n)
    proj_out = 2 * di * d
    layer = proj_in + conv + ssd_diag + ssd_state + proj_out
    return cfg["n_layer"] * layer + 2 * d * v


def flops_per_token(cfg) -> float:
    """Model FLOPs per token of a training step: forward and backward, the
    backward at twice the forward; recomputation (remat) not counted."""
    return 3.0 * forward_flops_per_token(cfg)


def layout(cfg) -> dict:
    """Parameter path -> (shape, dtype, init, scale)."""
    d, L = cfg["d_model"], cfg["n_layer"]
    di = cfg["expand"] * d
    n = cfg["d_state"]
    h = di // cfg["headdim"]
    w = cfg["d_conv"]
    v = padded_vocab(cfg)
    bf, f32 = cfg["dtype"], "float32"
    out = {
        "embed": ((v, d), bf, "normal", 0.02),
        "unembed": ((d, v), bf, "normal", 0.02),
        "final_norm": ((d,), bf, "ones", 0.0),
        "blocks/ln1": ((L, d), bf, "ones", 0.0),
        "blocks/ssm/w_z": ((L, d, di), bf, "normal", 1 / math.sqrt(d)),
        "blocks/ssm/w_x": ((L, d, di), bf, "normal", 1 / math.sqrt(d)),
        "blocks/ssm/w_bc": ((L, d, 2 * n), bf, "normal", 1 / math.sqrt(d)),
        "blocks/ssm/w_dt": ((L, d, h), bf, "normal", 1 / math.sqrt(d)),
        "blocks/ssm/conv_x": ((L, w, di), bf, "normal", 1 / math.sqrt(w)),
        "blocks/ssm/conv_bc": ((L, w, 2 * n), bf, "normal", 1 / math.sqrt(w)),
        "blocks/ssm/A_log": ((L, h), f32, "a_log", 0.0),
        "blocks/ssm/D": ((L, h), f32, "ones", 0.0),
        "blocks/ssm/dt_bias": ((L, h), f32, "dt_bias", 0.0),
        "blocks/ssm/norm": ((L, di), bf, "ones", 0.0),
        # out_proj rescaled by 1/sqrt(n_layer), Mamba's prenorm-residual init
        "blocks/ssm/w_out": ((L, di, d), bf, "normal",
                             1 / math.sqrt(di) / math.sqrt(L)),
    }
    if {f"blocks/ssm/{k}" for k in cfg["float32_params"]} != {
            k for k, v in out.items() if v[1] == f32}:
        raise ValueError(f"float32_params {cfg['float32_params']} do not "
                         "match the layout")
    return out


def _leaf(key, shape, dtype, init, scale):
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "normal":
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
    if init == "a_log":  # A = -U[1, 16]
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if init == "dt_bias":  # softplus(dt_bias) = dt, dt log-uniform [1e-3, 1e-1]
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        dt = jnp.maximum(dt, 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(init)


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def init_params(cfg, key) -> dict:
    """The bench's weights, from ``key``: one key per leaf, folded from a
    checksum of its path, so every leaf is the same in every process."""
    flat = {}
    for path, (shape, dtype, init, scale) in layout(cfg).items():
        k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
        flat[path] = _leaf(k, shape, jnp.dtype(dtype), init, scale)
    return nest(flat)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _round(x, dtype):
    """Round to an fp8 ``dtype`` with a per-tensor scale, back to float32."""
    top = float(jnp.finfo(dtype).max)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _einsum(eq, *ops):
    return jnp.einsum(eq, *ops, precision=lax.Precision.HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(eq, *ops):
    return _fp8_fwd(eq, *ops)[0]


def _fp8_fwd(eq, *ops):
    q = tuple(_round(o, jnp.float8_e4m3fn) for o in ops)
    return _einsum(eq, *q), q


def _fp8_bwd(eq, q, g):
    _, vjp = jax.vjp(functools.partial(_einsum, eq), *q)
    return vjp(_round(g, jnp.float8_e5m2))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(eq, compute, *ops):
    ops = [o.astype(jnp.float32) for o in ops]
    if compute == "fp8":
        return _fp8_einsum(eq, *ops)
    return _einsum(eq, *ops)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * \
        w.astype(jnp.float32)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _segsum(x):
    """x (..., T) -> (..., T, T): sum of x over (j, i] where j <= i, else -inf."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd(x, a, b, c, chunk, compute):
    """The paper's minimal chunked SSD. x (B, L, H, P) already scaled by dt,
    a (B, L, H) = dt * A, b and c (B, L, N) shared by the heads."""
    bsz, length, h, p = x.shape
    n = b.shape[-1]
    nc = length // chunk
    x = x.reshape(bsz, nc, chunk, h, p)
    b = b.reshape(bsz, nc, chunk, n)
    c = c.reshape(bsz, nc, chunk, n)
    a = jnp.moveaxis(a.reshape(bsz, nc, chunk, h), 3, 1)  # (B, H, C, Q)
    a_cum = jnp.cumsum(a, axis=-1)
    decay = jnp.exp(_segsum(a))  # (B, H, C, Q, Q)
    cb = _mm("bcln,bcsn->bcls", compute, c, b)
    y_diag = _mm("bcls,bhcls,bcshp->bclhp", compute, cb, decay, x)
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)
    states = _mm("bcln,bhcl,bclhp->bchpn", compute, b, decay_states, x)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    chunk_decay = jnp.exp(_segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = _mm("bhzc,bchpn->bzhpn", compute, chunk_decay, states)[:, :-1]
    y_off = _mm("bcln,bchpn,bhcl->bclhp", compute, c, states, jnp.exp(a_cum))
    return (y_diag + y_off).reshape(bsz, length, h, p)


def layer(h, w, cfg, compute):
    """One Mamba-2 block with its residual: h (B, L, d) in the residual's
    type, returned in the same type."""
    eps = cfg["norm_eps"]
    d = cfg["d_model"]
    di = cfg["expand"] * d
    n = cfg["d_state"]
    hp = cfg["headdim"]
    nh = di // hp
    wd = cfg["d_conv"]
    s = w["ssm"]
    u = _rms(h, w["ln1"], eps)
    z = _mm("bld,dk->blk", compute, u, s["w_z"])
    xs = _mm("bld,dk->blk", compute, u, s["w_x"])
    bc = _mm("bld,dk->blk", compute, u, s["w_bc"])
    dt = _mm("bld,dk->blk", compute, u, s["w_dt"])
    xbc = jnp.concatenate([xs, bc], axis=-1)
    conv_w = jnp.concatenate([s["conv_x"], s["conv_bc"]], axis=1).astype(jnp.float32)
    length = xbc.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (wd - 1, 0), (0, 0)))
    xbc = _silu(sum(padded[:, k:k + length] * conv_w[k] for k in range(wd)))
    xs, bm, cm = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + s["dt_bias"])
    a = -jnp.exp(s["A_log"])
    x = xs.reshape(*xs.shape[:2], nh, hp)
    y = ssd(x * dt[..., None], dt * a, bm, cm, cfg["chunk_size"], compute)
    y = y + s["D"][:, None] * x
    y = y.reshape(*y.shape[:2], di) * _silu(z)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps) * \
        s["norm"].astype(jnp.float32)
    out = _mm("blk,kd->bld", compute, y, s["w_out"])
    res = jnp.dtype(cfg["dtype"]) if not cfg["residual_in_fp32"] else jnp.float32
    return (h.astype(jnp.float32) + out).astype(res)


def loss_fn(params, tokens, labels, cfg, compute="f32"):
    res = jnp.dtype(cfg["dtype"]) if not cfg["residual_in_fp32"] else jnp.float32
    h = jnp.take(params["embed"], tokens, axis=0).astype(res)

    def body(h, w):
        return layer(h, w, cfg, compute), None

    h, _ = lax.scan(jax.checkpoint(body), h, params["blocks"])
    h = _rms(h, params["final_norm"], cfg["norm_eps"])
    logits = _mm("bld,dv->blv", compute, h, params["unembed"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def lr_at(opt, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    span = max(opt["total_steps"] - opt["warmup_steps"], 1)
    frac = jnp.clip((step - opt["warmup_steps"]) / span, 0.0, 1.0)
    return opt["lr"] * warm * (0.1 + 0.9 * 0.5 * (1.0 + jnp.cos(jnp.pi * frac)))


def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in flatten(tree).items()}


def make_step(cfg, compute="f32"):
    """One AdamW step of the plain model: (params, mu, nu, t, tokens,
    labels) -> (params, mu, nu, loss, per-leaf norms of the clipped
    gradient)."""
    opt = cfg["optimizer"]

    def step(params, mu, nu, t, tokens, labels):
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(loss_fn)(
                params, tokens, labels, cfg, compute)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in jax.tree.leaves(grads)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9))
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) * clip, grads)
        norms = _norms(grads)
        lr = lr_at(opt, t)
        b1, b2 = opt["b1"], opt["b2"]
        tf = t.astype(jnp.float32)

        def upd(p, g, m, v):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mhat = m / (1 - b1 ** tf)
            vhat = v / (1 - b2 ** tf)
            p32 = p.astype(jnp.float32)
            p32 = p32 - lr * (mhat / (jnp.sqrt(vhat) + opt["eps"])
                              + opt["weight_decay"] * p32)
            return p32.astype(p.dtype), m, v

        out = jax.tree.map(upd, params, grads, mu, nu)
        is_t = lambda x: isinstance(x, tuple)
        new_p = jax.tree.map(lambda o: o[0], out, is_leaf=is_t)
        new_m = jax.tree.map(lambda o: o[1], out, is_leaf=is_t)
        new_v = jax.tree.map(lambda o: o[2], out, is_leaf=is_t)
        return new_p, new_m, new_v, loss, norms

    return jax.jit(step, donate_argnums=(0, 1, 2))


def change_norms(cfg):
    """jitted (params, key) -> per-leaf norm of params - init_params(key)."""

    def f(params, key):
        p0 = init_params(cfg, key)
        return _norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            params, p0))

    return jax.jit(f)


def init_losses(cfg, key, batches):
    """The loss of ``init_params(cfg, key)`` on each batch."""
    params = jax.jit(lambda k: init_params(cfg, k))(key)
    loss = jax.jit(lambda p, t, l: loss_fn(p, t, l, cfg))
    with jax.default_matmul_precision("highest"):
        return [float(loss(params, jnp.asarray(b["tokens"]),
                           jnp.asarray(b["labels"]))) for b in batches]


def train(cfg, key, batches, compute="f32"):
    """Runs len(batches) steps from ``init_params(cfg, key)``. Returns the
    losses, the per-leaf norms of the first clipped gradient and the
    per-leaf norms of the parameters' change after the last step."""
    params = jax.jit(lambda k: init_params(cfg, k))(key)
    mu = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    nu = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    step = make_step(cfg, compute)
    losses, first = [], None
    for t, b in enumerate(batches, start=1):
        params, mu, nu, loss, norms = step(
            params, mu, nu, jnp.int32(t), jnp.asarray(b["tokens"]),
            jnp.asarray(b["labels"]))
        losses.append(float(loss))
        if first is None:
            first = {k: float(v) for k, v in norms.items()}
    del mu, nu
    change = {k: float(v) for k, v in change_norms(cfg)(params, key).items()}
    return losses, first, change
