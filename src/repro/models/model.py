"""Model assembly: parameter trees, train loss, and one-token decode for
every assigned architecture family.

All apply code is rank-centric shard_map body code.  Layer stacks are
``lax.scan`` over stacked parameters (leading L dim) with optional remat —
required to keep 95-layer compiles tractable.

Cache layout notes (decode):
  * attention kv:   (L, B, S_loc, kv_eff, hd)   S_loc context-parallel when
                    the batch cannot fill the data axis (KVCacheSpec)
  * MLA latent:     (L, B, S, r + rope_dim)     tiny, replicated over TP
  * SSD state:      (L, B, H_loc, p, n) + conv states (x | bc split because
                    their TP layouts differ)
  * hybrid:         SSD caches + one kv cache per shared-attn application
  * enc-dec:        decoder self kv + the encoder output (cross-attention
                    recomputes k/v from it — S_enc is small)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models import attention, blocks, mla as mla_mod, moe as moe_mod, ssm as ssm_mod
from repro.models.attention import KVCacheSpec
from repro.models.config import ModelConfig
from repro.models.layers import (
    chunked_vocab_xent,
    embed_lookup,
    gather_logits,
    rms_norm,
    vocab_parallel_logits,
    vocab_parallel_xent,
)
from repro.models.parallel import ParallelCtx, ParamDef

MOE_AUX_COEF = 0.01

# Named scopes of the train step's model layers (op metadata in the compiled
# HLO, read per layer from a device profile).  Backward ops of a scope sit
# under ``transpose(jvp(<scope>))``.
BLOCKS_SCOPE = "model.blocks"  # the layer stack
HEAD_SCOPE = "model.head"      # final norm, LM head, cross-entropy


def _stack(defs, L: int):
    """Add a leading stacked-layer dim to every ParamDef in a tree."""

    def one(d: ParamDef) -> ParamDef:
        return dataclasses.replace(
            d, shape=(L,) + d.shape, spec=P(*((None,) + tuple(d.spec)))
        )

    return jax.tree.map(one, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def _norm(cfg):
    return blocks.norm_def(cfg)


class Model:
    """One class covers all families; family dispatch is internal."""

    def __init__(self, cfg: ModelConfig, ctx: ParallelCtx):
        self.cfg = cfg
        self.ctx = ctx

    # ---------------- parameter definitions ----------------

    def _block_defs(self, *, cross: bool = False) -> dict:
        cfg, tp = self.cfg, self.ctx.tp_size
        fam = cfg.family
        if fam in ("dense", "vlm", "audio", "encdec"):
            d = {
                "ln1": _norm(cfg),
                "ln2": _norm(cfg),
                "attn": blocks.attn_defs(cfg, tp),
                "mlp": blocks.mlp_defs(cfg),
            }
            if cfg.mla is not None:
                d = {
                    "ln1": _norm(cfg),
                    "ln2": _norm(cfg),
                    "mla": blocks.mla_defs(cfg, tp),
                    "mlp": blocks.mlp_defs(cfg),
                }
            if cross:
                d["ln_cross"] = _norm(cfg)
                d["cross"] = blocks.attn_defs(cfg, tp)
            return d
        if fam == "moe":
            return {
                "ln1": _norm(cfg),
                "ln2": _norm(cfg),
                "attn": blocks.attn_defs(cfg, tp),
                "moe": blocks.moe_defs(cfg),
            }
        if fam == "ssm":
            return {"ln1": _norm(cfg), "ssm": blocks.ssm_defs(cfg)}
        if fam == "hybrid":
            return {"ln1": _norm(cfg), "ssm": blocks.ssm_defs(cfg)}
        raise ValueError(fam)

    def param_defs(self) -> dict:
        cfg = self.cfg
        v = cfg.padded_vocab()
        d = cfg.d_model
        defs: dict[str, Any] = {
            "embed": ParamDef((v, d), P("model", "data"), init="normal"),
            "unembed": ParamDef((d, v), P("data", "model"), init="scaled"),
            "final_norm": _norm(cfg),
            "blocks": _stack(self._block_defs(cross=cfg.family == "encdec"),
                             cfg.n_layers),
        }
        if cfg.family == "encdec":
            enc = {
                "ln1": _norm(cfg),
                "ln2": _norm(cfg),
                "attn": blocks.attn_defs(cfg, self.ctx.tp_size),
                "mlp": blocks.mlp_defs(cfg),
            }
            defs["enc_blocks"] = _stack(enc, cfg.n_enc_layers)
            defs["enc_norm"] = _norm(cfg)
        if cfg.family == "hybrid" and cfg.attn_every:
            # zamba2: ONE shared attention+mlp block applied every k layers
            defs["shared_attn"] = {
                "ln1": _norm(cfg),
                "ln2": _norm(cfg),
                "attn": blocks.attn_defs(cfg, self.ctx.tp_size),
                "mlp": blocks.mlp_defs(cfg),
            }
        return defs

    # ---------------- training forward / loss ----------------

    def _scan(self, h, stacked, body, with_aux: bool = False):
        ctx = self.ctx

        def f(carry, wl):
            if with_aux:
                out, aux = body(carry, wl)
                return out, aux
            return body(carry, wl), None

        if ctx.remat != "none":
            f = jax.checkpoint(f)
        with jax.named_scope(BLOCKS_SCOPE):
            h, auxs = lax.scan(f, h, stacked, unroll=ctx.scan_unroll)
        return (h, jnp.sum(auxs)) if with_aux else (h, None)

    def _backbone(self, h, params, *, positions, window=0, cross_kv=None):
        """Run the decoder/backbone stack over hidden states h."""
        cfg, ctx = self.cfg, self.ctx
        fam = cfg.family
        aux = jnp.float32(0.0)
        if fam in ("dense", "vlm", "audio") and cfg.mla is None:
            h, _ = self._scan(
                h,
                params["blocks"],
                lambda hh, wl: blocks.dense_block(
                    hh, wl, cfg, ctx, positions=positions, window=window
                ),
            )
        elif cfg.mla is not None:
            h, _ = self._scan(
                h,
                params["blocks"],
                lambda hh, wl: blocks.mla_block(hh, wl, cfg, ctx, positions=positions),
            )
        elif fam == "moe":
            h, aux = self._scan(
                h,
                params["blocks"],
                lambda hh, wl: blocks.moe_block(
                    hh, wl, cfg, ctx, positions=positions, window=window
                ),
                with_aux=True,
            )
        elif fam == "ssm":
            h, _ = self._scan(
                h, params["blocks"], lambda hh, wl: blocks.ssm_block(hh, wl, cfg, ctx)
            )
        elif fam == "hybrid":
            h = self._hybrid_train(h, params, positions=positions, window=window)
        elif fam == "encdec":
            h, _ = self._scan(
                h,
                params["blocks"],
                lambda hh, wl: blocks.dense_block(
                    hh, wl, cfg, ctx, positions=positions, cross_kv=cross_kv
                ),
            )
        else:
            raise ValueError(fam)
        return h, aux

    def _hybrid_train(self, h, params, *, positions, window=0):
        cfg, ctx = self.cfg, self.ctx
        k = cfg.attn_every
        n_groups = cfg.n_layers // k
        sa = params["shared_attn"]
        for g in range(n_groups):
            grp = jax.tree.map(lambda p: p[g * k : (g + 1) * k], params["blocks"])
            h, _ = self._scan(
                h, grp, lambda hh, wl: blocks.ssm_block(hh, wl, cfg, ctx)
            )
            h = blocks.dense_block(
                h, sa, cfg, ctx, positions=positions, window=window
            )
        rem = cfg.n_layers - n_groups * k
        if rem:
            grp = jax.tree.map(lambda p: p[-rem:], params["blocks"])
            h, _ = self._scan(
                h, grp, lambda hh, wl: blocks.ssm_block(hh, wl, cfg, ctx)
            )
        return h

    def _encode(self, params, enc_input):
        cfg, ctx = self.cfg, self.ctx
        positions = jnp.arange(enc_input.shape[1])
        h, _ = self._scan(
            enc_input.astype(jnp.dtype(cfg.dtype)),
            params["enc_blocks"],
            lambda hh, wl: blocks.dense_block(
                hh, wl, cfg, ctx, positions=positions, causal=False
            ),
        )
        return rms_norm(h, params["enc_norm"], cfg.norm_eps)

    def loss_fn(self, params, batch) -> jnp.ndarray:
        """batch: tokens (B,S), labels (B,S) [-1 = masked], optional
        prefix (B,n_prefix,d) [vlm/audio], enc_input (B,S_enc,d) [encdec]."""
        cfg, ctx = self.cfg, self.ctx
        tokens = batch["tokens"]
        h = embed_lookup(tokens, params["embed"], ctx)
        cross_kv = None
        if cfg.family == "encdec":
            cross_kv = self._encode(params, batch["enc_input"])
        if cfg.n_prefix and cfg.family in ("vlm", "audio"):
            prefix = batch["prefix"].astype(h.dtype)
            h = jnp.concatenate([prefix, h], axis=1)
        positions = jnp.arange(h.shape[1])
        h, aux = self._backbone(
            h, params, positions=positions, cross_kv=cross_kv,
            window=cfg.sliding_window if cfg.sliding_window else 0,
        )
        if cfg.n_prefix and cfg.family in ("vlm", "audio"):
            h = h[:, cfg.n_prefix :]
        with jax.named_scope(HEAD_SCOPE):
            h = rms_norm(h, params["final_norm"], cfg.norm_eps)
            labels = batch["labels"]
            mask = (labels >= 0).astype(jnp.float32)
            if cfg.loss_chunk:
                loss = chunked_vocab_xent(
                    h, params["unembed"], jnp.maximum(labels, 0), mask, ctx,
                    chunk=cfg.loss_chunk,
                )
            else:
                logits = vocab_parallel_logits(h, params["unembed"], ctx)
                loss = vocab_parallel_xent(logits, jnp.maximum(labels, 0), ctx,
                                           mask=mask)
            if cfg.family == "moe":
                loss = loss + MOE_AUX_COEF * aux / cfg.n_layers
            return loss

    # ---------------- costing hooks (see launch/costing.py) ----------------

    def block_apply(self, h, wl, *, positions, kind: str = "main"):
        """Apply ONE layer (family dispatch) — used by the dry-run's
        differential scan-body costing (XLA counts while bodies once)."""
        cfg, ctx = self.cfg, self.ctx
        if kind == "enc":
            return blocks.dense_block(h, wl, cfg, ctx, positions=positions,
                                      causal=False)
        if cfg.family == "encdec":
            # cross_kv the same length as the encoder output
            cross = jnp.zeros((h.shape[0], cfg.n_prefix or 128, cfg.d_model),
                              h.dtype)
            return blocks.dense_block(h, wl, cfg, ctx, positions=positions,
                                      cross_kv=cross)
        if cfg.mla is not None:
            return blocks.mla_block(h, wl, cfg, ctx, positions=positions)
        if cfg.family == "moe":
            out, _ = blocks.moe_block(h, wl, cfg, ctx, positions=positions)
            return out
        if cfg.family in ("ssm", "hybrid"):
            return blocks.ssm_block(h, wl, cfg, ctx)
        return blocks.dense_block(h, wl, cfg, ctx, positions=positions,
                                  window=cfg.sliding_window)

    def scan_trip_counts(self) -> list:
        """[(kind, trip_count, bodies_in_program)] for cost correction."""
        cfg = self.cfg
        if cfg.family == "hybrid":
            k = cfg.attn_every
            n_groups = cfg.n_layers // k
            return [("main", k, n_groups)]
        out = [("main", cfg.n_layers, 1)]
        if cfg.family == "encdec":
            out.append(("enc", cfg.n_enc_layers, 1))
        return out

    def block_defs_for(self, kind: str) -> dict:
        if kind == "enc":
            return {
                "ln1": _norm(self.cfg),
                "ln2": _norm(self.cfg),
                "attn": blocks.attn_defs(self.cfg, self.ctx.tp_size),
                "mlp": blocks.mlp_defs(self.cfg),
            }
        return self._block_defs(cross=self.cfg.family == "encdec")

    # ---------------- decode (one token) ----------------

    def cache_defs(self, batch_local: int, spec: KVCacheSpec) -> dict:
        """LOCAL cache shapes (the launcher maps them to global + specs)."""
        cfg, tp = self.cfg, self.ctx.tp_size
        L = cfg.n_layers
        hd = cfg.head_dim
        kvl = attention.kv_local_heads(cfg, tp)
        sl = spec.s_local
        out: dict[str, Any] = {}
        if cfg.mla is not None:
            out["mla"] = (L, batch_local, spec.s_total, mla_mod.mla_cache_dims(cfg))
            return out
        if cfg.family in ("dense", "vlm", "audio", "moe"):
            out["k"] = (L, batch_local, sl, kvl, hd)
            out["v"] = (L, batch_local, sl, kvl, hd)
            return out
        if cfg.family == "ssm":
            conv, state = ssm_mod.ssm_state_shapes(cfg, tp, batch_local)
            di_l = cfg.ssm.d_inner(cfg.d_model) // tp
            out["conv_x"] = (L,) + conv[:-1] + (di_l,)
            out["conv_bc"] = (L,) + conv[:-1] + (2 * cfg.ssm.d_state,)
            out["ssm"] = (L,) + state
            return out
        if cfg.family == "hybrid":
            conv, state = ssm_mod.ssm_state_shapes(cfg, tp, batch_local)
            di_l = cfg.ssm.d_inner(cfg.d_model) // tp
            n_groups = cfg.n_layers // cfg.attn_every
            out["conv_x"] = (L,) + conv[:-1] + (di_l,)
            out["conv_bc"] = (L,) + conv[:-1] + (2 * cfg.ssm.d_state,)
            out["ssm"] = (L,) + state
            out["k"] = (n_groups, batch_local, sl, kvl, hd)
            out["v"] = (n_groups, batch_local, sl, kvl, hd)
            return out
        if cfg.family == "encdec":
            out["k"] = (L, batch_local, sl, kvl, hd)
            out["v"] = (L, batch_local, sl, kvl, hd)
            out["enc_out"] = (batch_local, cfg.n_prefix or 128, cfg.d_model)
            return out
        raise ValueError(cfg.family)

    def decode_fn(self, params, cache, tokens, pos, spec: KVCacheSpec):
        """One decode step.  tokens: (B, 1) int32; pos: scalar int32.

        Returns (logits (B, 1, V_pad), new_cache).
        """
        cfg, ctx = self.cfg, self.ctx
        h = embed_lookup(tokens, params["embed"], ctx)
        fam = cfg.family

        def attn_layer(hh, wl, ck, cv):
            a, nk, nv = attention.attention_decode(
                rms_norm(hh, wl["ln1"], cfg.norm_eps), wl["attn"], ck, cv,
                pos, cfg, ctx, spec,
            )
            return hh + a, nk, nv

        new_cache = dict(cache)
        if fam in ("dense", "vlm", "audio", "moe") and cfg.mla is None:

            def step(hh, xs):
                wl, ck, cv = xs
                hh, nk, nv = attn_layer(hh, wl, ck, cv)
                if fam == "moe":
                    m, _ = moe_mod.moe_ffn(
                        rms_norm(hh, wl["ln2"], cfg.norm_eps), wl["moe"], cfg, ctx
                    )
                else:
                    m = blocks._mlp(
                        rms_norm(hh, wl["ln2"], cfg.norm_eps), wl["mlp"], ctx
                    )
                return hh + m, (nk, nv)

            h, (nk, nv) = lax.scan(
                step, h, (params["blocks"], cache["k"], cache["v"]),
                unroll=ctx.scan_unroll,
            )
            new_cache["k"], new_cache["v"] = nk, nv
        elif cfg.mla is not None:

            def step(hh, xs):
                wl, cl = xs
                a, ncl = mla_mod.mla_decode(
                    rms_norm(hh, wl["ln1"], cfg.norm_eps), wl["mla"], cl, pos,
                    cfg, ctx,
                )
                hh = hh + a
                m = blocks._mlp(rms_norm(hh, wl["ln2"], cfg.norm_eps), wl["mlp"], ctx)
                return hh + m, ncl

            h, ncl = lax.scan(step, h, (params["blocks"], cache["mla"]),
                              unroll=ctx.scan_unroll)
            new_cache["mla"] = ncl
        elif fam == "ssm":

            def step(hh, xs):
                wl, cx, cbc, cs = xs
                di_l = cx.shape[-1]
                y, nconv, nssm = ssm_mod.ssm_decode(
                    rms_norm(hh, wl["ln1"], cfg.norm_eps), wl["ssm"],
                    jnp.concatenate([cx, cbc], axis=-1), cs, cfg, ctx,
                )
                return hh + y, (nconv[..., :di_l], nconv[..., di_l:], nssm)

            h, (ncx, ncbc, nssm) = lax.scan(
                step, h,
                (params["blocks"], cache["conv_x"], cache["conv_bc"], cache["ssm"]),
                unroll=ctx.scan_unroll,
            )
            new_cache["conv_x"], new_cache["conv_bc"], new_cache["ssm"] = (
                ncx, ncbc, nssm,
            )
        elif fam == "hybrid":
            h, new_cache = self._hybrid_decode(params, cache, h, pos, spec)
        elif fam == "encdec":
            enc_out = cache["enc_out"].astype(h.dtype)

            def step(hh, xs):
                wl, ck, cv = xs
                hh, nk, nv = attn_layer(hh, wl, ck, cv)
                c = attention.attention_train(
                    rms_norm(hh, wl["ln_cross"], cfg.norm_eps), wl["cross"],
                    cfg, ctx, positions=pos[None], causal=False,
                    cross_kv=enc_out,
                )
                hh = hh + c
                m = blocks._mlp(rms_norm(hh, wl["ln2"], cfg.norm_eps), wl["mlp"], ctx)
                return hh + m, (nk, nv)

            h, (nk, nv) = lax.scan(
                step, h, (params["blocks"], cache["k"], cache["v"]),
                unroll=ctx.scan_unroll,
            )
            new_cache["k"], new_cache["v"] = nk, nv
        else:
            raise ValueError(fam)

        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = vocab_parallel_logits(h, params["unembed"], ctx)
        return gather_logits(logits, ctx), new_cache

    def _hybrid_decode(self, params, cache, h, pos, spec: KVCacheSpec):
        cfg, ctx = self.cfg, self.ctx
        k = cfg.attn_every
        n_groups = cfg.n_layers // k
        sa = params["shared_attn"]
        new_cache = dict(cache)
        ncx, ncbc, nssm = [], [], []
        nk, nv = [], []

        def ssm_step(hh, xs):
            wl, cx, cbc, cs = xs
            di_l = cx.shape[-1]
            y, nconv, nss = ssm_mod.ssm_decode(
                rms_norm(hh, wl["ln1"], cfg.norm_eps), wl["ssm"],
                jnp.concatenate([cx, cbc], axis=-1), cs, cfg, ctx,
            )
            return hh + y, (nconv[..., :di_l], nconv[..., di_l:], nss)

        for g in range(n_groups):
            sl = slice(g * k, (g + 1) * k)
            grp = jax.tree.map(lambda p: p[sl], params["blocks"])
            h, (cx, cbc, cs) = lax.scan(
                ssm_step, h,
                (grp, cache["conv_x"][sl], cache["conv_bc"][sl], cache["ssm"][sl]),
                unroll=ctx.scan_unroll,
            )
            ncx.append(cx)
            ncbc.append(cbc)
            nssm.append(cs)
            a, gk, gv = attention.attention_decode(
                rms_norm(h, sa["ln1"], cfg.norm_eps), sa["attn"],
                cache["k"][g], cache["v"][g], pos, cfg, ctx, spec,
            )
            h = h + a
            m = blocks._mlp(rms_norm(h, sa["ln2"], cfg.norm_eps), sa["mlp"], ctx)
            h = h + m
            nk.append(gk)
            nv.append(gv)
        rem = cfg.n_layers - n_groups * k
        if rem:
            grp = jax.tree.map(lambda p: p[-rem:], params["blocks"])
            h, (cx, cbc, cs) = lax.scan(
                ssm_step, h,
                (grp, cache["conv_x"][-rem:], cache["conv_bc"][-rem:],
                 cache["ssm"][-rem:]),
                unroll=ctx.scan_unroll,
            )
            ncx.append(cx)
            ncbc.append(cbc)
            nssm.append(cs)
        new_cache["conv_x"] = jnp.concatenate(ncx)
        new_cache["conv_bc"] = jnp.concatenate(ncbc)
        new_cache["ssm"] = jnp.concatenate(nssm)
        new_cache["k"] = jnp.stack(nk)
        new_cache["v"] = jnp.stack(nv)
        return h, new_cache
