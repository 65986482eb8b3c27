"""Model assembly: decoder-only / enc-dec transformers over heterogeneous
block patterns (attention variants, SSD, RG-LRU), with lax.scan layer stacks,
KV/state caches, and parallel param/sharding-spec construction.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ATTN_KINDS, ArchConfig
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.common import NO_SHARD, ShardCtx, embed_init, layer_norm, rms_norm, softcap


# ---------------------------------------------------------------------------
# norms

def norm_init(cfg: ArchConfig, dtype) -> dict:
    p = {"w": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm_type == "ln":
        p["w"] = jnp.ones((cfg.d_model,), dtype)
        p["b"] = jnp.zeros((cfg.d_model,), dtype)
    elif cfg.norm_type == "rms":
        # gemma-style (1+w): init w to zero
        p["w"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def norm_specs(cfg: ArchConfig) -> dict:
    s = {"w": P(None)}
    if cfg.norm_type == "ln":
        s["b"] = P(None)
    return s


@jax.named_scope("norm")
def norm_apply(cfg: ArchConfig, p: dict, x):
    if cfg.norm_type == "ln":
        return layer_norm(x, p["w"], p["b"], cfg.norm_eps)
    return rms_norm(x, p["w"], cfg.norm_eps, plus_one=True)


# ---------------------------------------------------------------------------
# blocks

def _has_ffn(cfg: ArchConfig, kind: str) -> bool:
    return cfg.d_ff > 0 and kind != "ssm"


def block_init(cfg: ArchConfig, key, dtype, kind: str, *, cross: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": norm_init(cfg, dtype)}
    if kind in ATTN_KINDS:
        p["mixer"] = attn_mod.attn_init(cfg, ks[0], dtype)
    elif kind == "ssm":
        p["mixer"] = ssm_mod.ssm_init(cfg, ks[0], dtype)
    elif kind == "rglru":
        p["mixer"] = rglru_mod.rglru_init(cfg, ks[0], dtype)
    else:
        raise ValueError(kind)
    if cfg.post_norms:
        p["ln1_post"] = norm_init(cfg, dtype)
    if cross:
        p["ln_cross"] = norm_init(cfg, dtype)
        p["cross"] = attn_mod.attn_init(cfg, ks[1], dtype, cross=True)
    if _has_ffn(cfg, kind):
        p["ln2"] = norm_init(cfg, dtype)
        if cfg.moe is not None and kind in ATTN_KINDS:
            p["ffn"] = mlp_mod.moe_init(cfg, ks[2], dtype)
        else:
            p["ffn"] = mlp_mod.mlp_init(cfg, ks[2], dtype)
        if cfg.post_norms:
            p["ln2_post"] = norm_init(cfg, dtype)
    return p


def block_specs(cfg: ArchConfig, kind: str, tp: str = "model", *, cross: bool = False) -> dict:
    s: Dict[str, Any] = {"ln1": norm_specs(cfg)}
    if kind in ATTN_KINDS:
        s["mixer"] = attn_mod.attn_specs(cfg, tp)
    elif kind == "ssm":
        s["mixer"] = ssm_mod.ssm_specs(cfg, tp)
    elif kind == "rglru":
        s["mixer"] = rglru_mod.rglru_specs(cfg, tp)
    if cfg.post_norms:
        s["ln1_post"] = norm_specs(cfg)
    if cross:
        s["ln_cross"] = norm_specs(cfg)
        s["cross"] = attn_mod.attn_specs(cfg, tp, cross=True)
    if _has_ffn(cfg, kind):
        s["ln2"] = norm_specs(cfg)
        if cfg.moe is not None and kind in ATTN_KINDS:
            s["ffn"] = mlp_mod.moe_specs(cfg, tp)
        else:
            s["ffn"] = mlp_mod.mlp_specs(cfg, tp)
        if cfg.post_norms:
            s["ln2_post"] = norm_specs(cfg)
    return s


def block_apply(
    cfg: ArchConfig,
    p: dict,
    x,
    *,
    kind: str,
    ctx: ShardCtx,
    positions,
    cache: Optional[dict],
    cache_pos,
    enc_out=None,
):
    """Returns (x, new_cache, aux_loss_scalar)."""
    aux = jnp.zeros((), jnp.float32)
    h = norm_apply(cfg, p["ln1"], x)
    new_cache = None
    # enc-dec block caches carry the banked encoder K/V ('ek'/'ev') next to
    # the mixer's own state — split them off before the mixer sees the dict
    cross_cache = None
    self_cache = cache
    if cache is not None and "ek" in cache:
        cross_cache = {"ek": cache["ek"], "ev": cache["ev"]}
        self_cache = {n: c for n, c in cache.items() if n not in ("ek", "ev")}
    if kind in ATTN_KINDS:
        out, new_cache = attn_mod.attn_apply(
            cfg, p["mixer"], h, kind=kind, ctx=ctx, positions=positions,
            cache=self_cache, cache_pos=cache_pos,
        )
    elif kind == "ssm":
        out, new_cache = ssm_mod.ssm_apply(cfg, p["mixer"], h, ctx,
                                           cache=self_cache)
    elif kind == "rglru":
        out, new_cache = rglru_mod.rglru_apply(cfg, p["mixer"], h, ctx,
                                               cache=self_cache)
    else:
        raise ValueError(kind)
    if cfg.post_norms:
        out = norm_apply(cfg, p["ln1_post"], ctx.residual(out))
    x = ctx.residual(x + out)

    if "cross" in p:
        hc = norm_apply(cfg, p["ln_cross"], x)
        out, new_cross = attn_mod.attn_apply(
            cfg, p["cross"], hc, kind="attn_bidir", ctx=ctx,
            positions=positions, kv_x=enc_out, use_rope=False,
            cross_cache=cross_cache,
        )
        x = ctx.residual(x + out)
        if cross_cache is not None:
            new_cache = dict(new_cache, **new_cross)

    if _has_ffn(cfg, kind):
        h2 = norm_apply(cfg, p["ln2"], x)
        if cfg.moe is not None and kind in ATTN_KINDS:
            # §Perf A1: expert-parallel (shard_map all-to-all) dispatch on a
            # mesh; REPRO_BASELINE_MOE=1 restores the baseline einsum path.
            import os as _os

            if ctx.mesh is not None and not _os.environ.get("REPRO_BASELINE_MOE"):
                out, moe_aux = mlp_mod.moe_apply_expert_parallel(
                    cfg, p["ffn"], h2, ctx
                )
            else:
                out, moe_aux = mlp_mod.moe_apply(cfg, p["ffn"], h2, ctx)
            aux = aux + moe_aux["moe_aux_loss"]
        else:
            out = mlp_mod.mlp_apply(cfg, p["ffn"], h2, ctx)
        if cfg.post_norms:
            out = norm_apply(cfg, p["ln2_post"], ctx.residual(out))
        x = ctx.residual(x + out)
    return x, new_cache, aux


def init_block_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int, dtype):
    if kind in ATTN_KINDS:
        c = attn_mod.init_kv_cache(cfg, batch, max_len, dtype, kind=kind)
    elif kind == "ssm":
        c = ssm_mod.init_ssm_cache(cfg, batch, dtype)
    elif kind == "rglru":
        c = rglru_mod.init_rglru_cache(cfg, batch, dtype)
    else:
        raise ValueError(kind)
    if cfg.encoder is not None:
        # EVERY enc-dec decoder block cross-attends (attention, SSD and
        # rgLRU alike — block_init gives them all a cross module): bank the
        # encoder K/V next to the mixer's own state
        c = dict(c, **attn_mod.init_cross_kv_cache(cfg, batch, dtype))
    return c


# ---------------------------------------------------------------------------
# model

def _split_layers(cfg: ArchConfig) -> Tuple[int, int]:
    pat = len(cfg.layer_pattern)
    return cfg.n_layers // pat, cfg.n_layers % pat


@dataclass
class Model:
    """Functional model bundle for one architecture."""

    cfg: ArchConfig
    ctx: ShardCtx = NO_SHARD
    param_dtype: Any = jnp.float32
    remat: bool = True
    # Dry-run/roofline mode: fully unroll the layer scans. XLA's
    # cost_analysis counts a while-loop body ONCE (not ×trip-count), so
    # straight-line HLO is required for exact FLOP/collective accounting.
    scan_unroll: bool = False

    def _scan(self, body, carry, xs):
        unroll = len(jax.tree.leaves(xs)[0]) if self.scan_unroll else 1
        return jax.lax.scan(body, carry, xs, unroll=unroll)

    # ---- params ----------------------------------------------------------
    def init(self, key) -> dict:
        cfg, dt = self.cfg, self.param_dtype
        n_cyc, n_tail = _split_layers(cfg)
        cross = cfg.encoder is not None
        keys = jax.random.split(key, 8)
        vp = cfg.padded_vocab()

        params: Dict[str, Any] = {
            "embed": embed_init(keys[0], (vp, cfg.d_model), dt),
            "final_norm": norm_init(cfg, dt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = embed_init(keys[1], (cfg.d_model, vp), dt)
        if not cfg.use_rope and any(k in ATTN_KINDS for k in cfg.layer_pattern):
            params["pos_embed"] = embed_init(keys[2], (cfg.max_position, cfg.d_model), dt)

        def cycle_init(k):
            kk = jax.random.split(k, len(cfg.layer_pattern))
            return tuple(
                block_init(cfg, kk[i], dt, kind, cross=cross)
                for i, kind in enumerate(cfg.layer_pattern)
            )

        if n_cyc > 0:
            params["layers"] = jax.vmap(cycle_init)(jax.random.split(keys[3], n_cyc))
        params["tail"] = tuple(
            block_init(cfg, k, dt, cfg.layer_pattern[i], cross=cross)
            for i, k in enumerate(jax.random.split(keys[4], n_tail))
        ) if n_tail else ()

        if cfg.encoder is not None:
            ek = jax.random.split(keys[5], cfg.encoder.n_layers + 1)

            def enc_cycle_init(k):
                return (block_init(cfg, k, dt, "attn_bidir"),)

            params["encoder"] = {
                "layers": jax.vmap(enc_cycle_init)(
                    jax.random.split(ek[0], cfg.encoder.n_layers)
                ),
                "final_norm": norm_init(cfg, dt),
            }
        return params

    def param_specs(self) -> dict:
        cfg = self.cfg
        tp = self.ctx.tp or "model"
        n_cyc, n_tail = _split_layers(cfg)
        cross = cfg.encoder is not None

        def stack(spec_tree):
            return jax.tree.map(
                lambda s: P(*((None,) + tuple(s))), spec_tree,
                is_leaf=lambda s: isinstance(s, P),
            )

        specs: Dict[str, Any] = {
            "embed": P(tp, None),
            "final_norm": norm_specs(cfg),
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = P(None, tp)
        if not cfg.use_rope and any(k in ATTN_KINDS for k in cfg.layer_pattern):
            specs["pos_embed"] = P(None, None)
        cyc = tuple(
            block_specs(cfg, kind, tp, cross=cross) for kind in cfg.layer_pattern
        )
        if n_cyc > 0:
            specs["layers"] = stack(cyc)
        specs["tail"] = tuple(
            block_specs(cfg, cfg.layer_pattern[i], tp, cross=cross)
            for i in range(n_tail)
        )
        if cfg.encoder is not None:
            specs["encoder"] = {
                "layers": stack((block_specs(cfg, "attn_bidir", tp),)),
                "final_norm": norm_specs(cfg),
            }
        return specs

    # ---- caches ----------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        cfg = self.cfg
        n_cyc, n_tail = _split_layers(cfg)

        def one_cycle(_):
            return tuple(
                init_block_cache(cfg, kind, batch, max_len, dtype)
                for kind in cfg.layer_pattern
            )

        cache: Dict[str, Any] = {}
        if n_cyc > 0:
            cache["layers"] = jax.vmap(one_cycle)(jnp.arange(n_cyc))
        cache["tail"] = tuple(
            init_block_cache(cfg, cfg.layer_pattern[i], batch, max_len, dtype)
            for i in range(n_tail)
        )
        return cache

    def cache_specs(self, cache) -> dict:
        """Shard caches: batch over dp if divisible, else KV seq over tp
        (context-parallel decode for batch=1 long-context)."""
        ctx = self.ctx
        mesh = ctx.mesh
        if mesh is None:
            return jax.tree.map(lambda x: P(), cache)
        dp_size = 1
        for a in ctx.dp:
            dp_size *= mesh.shape[a]

        def spec_for(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            stacked = any(
                getattr(q, "key", None) == "layers" for q in path
            )
            lead = (None,) if stacked else ()
            b_axis = ctx.dp if leaf.shape[len(lead)] % dp_size == 0 else None
            if name in ("k", "v", "ek", "ev"):
                # (B, T, kvh, hd): batch over dp; if batch unshardable,
                # sequence over dp AND tp (long_500k context parallelism)
                if b_axis is not None:
                    return P(*lead, b_axis, ctx.tp, None, None)
                return P(*lead, None, tuple(ctx.dp) + (ctx.tp,), None, None)
            if name == "conv":
                return P(*lead, b_axis, None, ctx.tp)
            if name == "h":
                rest = (ctx.tp,) + (None,) * (leaf.ndim - len(lead) - 2)
                return P(*lead, b_axis, *rest)
            return P(*([None] * leaf.ndim))

        from repro.models.common import sanitize_spec

        return jax.tree_util.tree_map_with_path(
            lambda pth, leaf: sanitize_spec(dict(mesh.shape), leaf.shape, spec_for(pth, leaf)),
            cache,
        )

    # ---- forward ---------------------------------------------------------
    @jax.named_scope("embed")
    def _embed(self, params, tokens, positions):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.scale_embeddings:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if "pos_embed" in params:
            x = x + jnp.take(params["pos_embed"], positions, axis=0)[None]
        return self.ctx.residual(x)

    def _encode(self, params, enc_input):
        """enc_input: precomputed frame embeddings (B, T_enc, d) (stub)."""
        cfg = self.cfg
        x = self.ctx.batch(enc_input)
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)

        def cycle(x, lp):
            x, _, _ = block_apply(
                cfg, lp[0], x, kind="attn_bidir", ctx=self.ctx,
                positions=pos, cache=None, cache_pos=None,
            )
            return x, None

        body = jax.checkpoint(cycle) if self.remat else cycle
        x, _ = self._scan(body, x, params["encoder"]["layers"])
        return norm_apply(cfg, params["encoder"]["final_norm"], x)

    def _trunk(self, params, x, positions, cache, cache_pos, enc_out):
        cfg = self.cfg
        n_cyc, n_tail = _split_layers(cfg)
        aux0 = jnp.zeros((), jnp.float32)

        def cycle(carry, xs):
            x, aux = carry
            lp, lc = xs
            new_cs = []
            for i, kind in enumerate(cfg.layer_pattern):
                x, nc, a = block_apply(
                    cfg, lp[i], x, kind=kind, ctx=self.ctx, positions=positions,
                    cache=None if lc is None else lc[i],
                    cache_pos=cache_pos, enc_out=enc_out,
                )
                aux = aux + a
                new_cs.append(nc)
            return (x, aux), tuple(new_cs)

        body = jax.checkpoint(cycle) if self.remat else cycle

        new_cache: Dict[str, Any] = {}
        if n_cyc > 0:
            lc = cache["layers"] if cache is not None else None
            if lc is None:
                (x, aux), _ = self._scan(
                    lambda c, lp: body(c, (lp, None)), (x, aux0), params["layers"]
                )
            else:
                (x, aux), new_lc = self._scan(
                    body, (x, aux0), (params["layers"], lc)
                )
                new_cache["layers"] = new_lc
        else:
            aux = aux0

        tail_caches = []
        for i in range(n_tail):
            lc_i = cache["tail"][i] if cache is not None else None
            x, nc, a = block_apply(
                cfg, params["tail"][i], x, kind=cfg.layer_pattern[i], ctx=self.ctx,
                positions=positions, cache=lc_i, cache_pos=cache_pos, enc_out=enc_out,
            )
            aux = aux + a
            tail_caches.append(nc)
        if cache is not None:
            new_cache["tail"] = tuple(tail_caches)
        return x, aux, (new_cache if cache is not None else None)

    @jax.named_scope("loss_head")
    def _logits(self, params, x):
        cfg = self.cfg
        x = norm_apply(cfg, params["final_norm"], x)
        head = (
            params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        )
        logits = jnp.einsum("bsd,dv->bsv", x, head).astype(jnp.float32)
        logits = softcap(logits, cfg.final_softcap)
        return self.ctx.cons(logits, P(self.ctx.dp, None, self.ctx.tp))

    def forward(self, params, tokens, *, enc_input=None, positions=None):
        """Teacher-forced forward. Returns (logits, aux)."""
        if positions is None:
            positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        enc_out = (
            self._encode(params, enc_input) if self.cfg.encoder is not None else None
        )
        x = self._embed(params, tokens, positions)
        x, aux, _ = self._trunk(params, x, positions, None, None, enc_out)
        return self._logits(params, x), {"moe_aux_loss": aux}

    def prefill(self, params, tokens, cache, *, enc_input=None):
        """Forward that also fills the cache from position 0."""
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        enc_out = (
            self._encode(params, enc_input) if self.cfg.encoder is not None else None
        )
        x = self._embed(params, tokens, positions)
        x, aux, new_cache = self._trunk(
            params, x, positions, cache, jnp.int32(0), enc_out
        )
        return self._logits(params, x), new_cache

    def decode_step(self, params, cache, tokens, pos, *, enc_out=None):
        """One-token decode. tokens: (B,1); pos: scalar int32 (write index)."""
        positions = pos + jnp.arange(1, dtype=jnp.int32)
        x = self._embed(params, tokens, positions)
        x, _, new_cache = self._trunk(params, x, positions, cache, pos, enc_out)
        return self._logits(params, x), new_cache

    # ---- continuous-batching decode (repro.serve) ------------------------
    def init_slot_cache(self, slots: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        """Slot-stacked cache for continuous batching: every leaf of the
        single-sequence cache gains a LEADING slot axis, so each slot can sit
        at its own decode position (`decode_slots` vmaps over it)."""
        return jax.vmap(lambda _: self.init_cache(1, max_len, dtype))(
            jnp.arange(slots)
        )

    def decode_slots(self, params, cache, tokens, pos):
        """Per-slot one-token decode over an `init_slot_cache` cache:
        ``tokens`` (slots,) int32 current token per slot, ``pos`` (slots,)
        int32 per-slot write index — positions are ragged across slots.
        Returns (logits (slots, vocab_padded), new_cache)."""

        def one(c, t, p):
            logits, nc = self.decode_step(params, c, t.reshape(1, 1), p)
            return logits[0, 0], nc

        return jax.vmap(one)(cache, tokens, pos)


def build_model(cfg: ArchConfig, ctx: ShardCtx = NO_SHARD, *, param_dtype=jnp.float32,
                remat: bool = True) -> Model:
    return Model(cfg=cfg, ctx=ctx, param_dtype=param_dtype, remat=remat)
