"""Dense MLP (SwiGLU/GeGLU/plain) and Mixture-of-Experts FFN.

TP layout (paper §3.1, Eq. 1-3): up/gate projections column-partitioned,
down projection row-partitioned over the `model` axis. MoE: the expert
dimension is sharded over `model` (expert parallelism — 16 experts/16 ranks
for llama4, 8 experts/rank for arctic), dispatch/combine is a sort-based
capacity-bounded scatter (drop on overflow), the standard TPU-friendly
formulation (no (N,E,C) one-hot blowup).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, MoESpec
from repro.models.common import ShardCtx, act_fn, dense_init


# ---------------------------------------------------------------------------
# dense MLP

def mlp_init(cfg: ArchConfig, key, dtype, d_ff: Optional[int] = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(ks[0], (d, ff), d, dtype),
        "w_down": dense_init(ks[1], (ff, d), ff, dtype),
    }
    if cfg.ffn_gated:
        p["w_gate"] = dense_init(ks[2], (d, ff), d, dtype)
    return p


def mlp_specs(cfg: ArchConfig, tp: str = "model") -> dict:
    s = {"w_up": P(None, tp), "w_down": P(tp, None)}
    if cfg.ffn_gated:
        s["w_gate"] = P(None, tp)
    return s


@jax.named_scope("mlp")
def mlp_apply(cfg: ArchConfig, p: dict, x, ctx: ShardCtx):
    act = act_fn(cfg.ffn_act)
    h = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    if cfg.ffn_gated:
        h = act(jnp.einsum("bsd,df->bsf", x, p["w_gate"])) * h
    else:
        h = act(h)
    h = ctx.hidden(h)
    return ctx.batch(jnp.einsum("bsf,fd->bsd", h, p["w_down"]))


# ---------------------------------------------------------------------------
# MoE

def moe_init(cfg: ArchConfig, key, dtype) -> dict:
    assert cfg.moe is not None
    m, d, ff = cfg.moe, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, m.n_experts), d, jnp.float32),
        "w_up": dense_init(ks[1], (m.n_experts, d, ff), d, dtype),
        "w_down": dense_init(ks[2], (m.n_experts, ff, d), ff, dtype),
    }
    if cfg.ffn_gated:
        p["w_gate"] = dense_init(ks[3], (m.n_experts, d, ff), d, dtype)
    if m.shared_expert:
        p["shared"] = mlp_init(cfg, ks[4], dtype)
    if m.dense_residual:
        p["dense"] = mlp_init(cfg, ks[4], dtype)
    return p


def moe_specs(cfg: ArchConfig, tp: str = "model") -> dict:
    assert cfg.moe is not None
    s = {
        "router": P(None, None),
        "w_up": P(tp, None, None),   # expert-parallel over the scale-up domain
        "w_down": P(tp, None, None),
    }
    if cfg.ffn_gated:
        s["w_gate"] = P(tp, None, None)
    if cfg.moe.shared_expert:
        s["shared"] = mlp_specs(cfg, tp)
    if cfg.moe.dense_residual:
        s["dense"] = mlp_specs(cfg, tp)
    return s


def _route(m: MoESpec, logits):
    """(N,E) router logits -> (N,k) expert ids + fp32 combine weights."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return idx, w, probs


@jax.named_scope("mlp")
def moe_apply(cfg: ArchConfig, p: dict, x, ctx: ShardCtx) -> Tuple[jnp.ndarray, dict]:
    """Returns (out, aux) — aux carries the load-balance loss (Switch-style)."""
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    e, k = m.n_experts, m.top_k
    cap = int(max(1, round(n * k / e * m.capacity_factor)))
    act = act_fn(cfg.ffn_act)
    xf = x.reshape(n, d)

    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), p["router"])
    idx, wts, probs = _route(m, logits)  # (N,k)

    # ---- sort-based dispatch -------------------------------------------
    flat_e = idx.reshape(-1)                      # (N*k,) expert of each slot
    order = jnp.argsort(flat_e)                   # stable
    sorted_e = flat_e[order]
    # position within expert = rank among same-expert slots
    start = jnp.searchsorted(sorted_e, jnp.arange(e))
    pos_in_e = jnp.arange(n * k) - start[sorted_e]
    keep = pos_in_e < cap                          # overflow drops (std. Switch)
    dest = jnp.where(keep, sorted_e * cap + pos_in_e, e * cap)  # OOB => dropped

    tok = order // k                               # source token of each slot
    buf = jnp.zeros((e * cap, d), x.dtype)
    buf = buf.at[dest].set(xf[tok], mode="drop")
    buf = buf.reshape(e, cap, d)
    buf = ctx.cons(buf, P(ctx.tp, None, None))     # expert-parallel buffers

    h = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    if cfg.ffn_gated:
        h = act(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * h
    else:
        h = act(h)
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"]).reshape(e * cap, d)
    y = ctx.cons(y.reshape(e, cap, d), P(ctx.tp, None, None)).reshape(e * cap, d)

    # ---- combine --------------------------------------------------------
    gathered = jnp.where(keep[:, None], y[dest], 0)      # (N*k, d) sorted order
    slot_w = wts.reshape(-1)[order]
    contrib = gathered * slot_w[:, None].astype(x.dtype)
    out = jnp.zeros((n, d), x.dtype).at[tok].add(contrib)
    out = out.reshape(b, s, d)

    if m.shared_expert:
        out = out + mlp_apply(cfg, p["shared"], x, ctx)
    if m.dense_residual:
        out = out + mlp_apply(cfg, p["dense"], x, ctx)

    # Switch load-balance aux loss: E * sum_e f_e * P_e
    f = jnp.zeros(e, jnp.float32).at[flat_e].add(1.0) / (n * k)
    pmean = probs.mean(0)
    aux = {"moe_aux_loss": e * jnp.sum(f * pmean) * m.router_aux_coef}
    return ctx.batch(out), aux


def _local_dispatch_compute(cfg: ArchConfig, p_local, xf, cap: int):
    """Sort-based dispatch + expert compute for one rank's token slice,
    with experts split across the TP axis via all-to-all (expert parallelism).

    xf: (n, d) local tokens; p_local experts already (E/tp, d, ff).
    Runs INSIDE shard_map. Returns (out (n, d), f, pmean) for the aux loss.
    """
    m = cfg.moe
    act = act_fn(cfg.ffn_act)
    n, d = xf.shape
    e, k = m.n_experts, m.top_k

    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), p_local["router"])
    idx, wts, probs = _route(m, logits)

    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(e))
    pos_in_e = jnp.arange(n * k) - start[sorted_e]
    keep = pos_in_e < cap
    dest = jnp.where(keep, sorted_e * cap + pos_in_e, e * cap)
    tok = order // k

    buf = jnp.zeros((e * cap, d), xf.dtype).at[dest].set(xf[tok], mode="drop")
    buf = buf.reshape(e, cap, d)

    # ---- expert parallelism: one all-to-all ships each expert's slots to
    # the rank that owns it (paper's NVL-domain all-to-all -> ICI)
    buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                             tiled=True)              # (E/tp, cap*tp, d)
    h = jnp.einsum("ecd,edf->ecf", buf, p_local["w_up"])
    if cfg.ffn_gated:
        h = act(jnp.einsum("ecd,edf->ecf", buf, p_local["w_gate"])) * h
    else:
        h = act(h)
    y = jnp.einsum("ecf,efd->ecd", h, p_local["w_down"])
    y = jax.lax.all_to_all(y, "model", split_axis=1, concat_axis=0,
                           tiled=True)                # (E, cap, d)

    gathered = jnp.where(keep[:, None], y.reshape(e * cap, d)[dest], 0)
    slot_w = wts.reshape(-1)[order]
    out = jnp.zeros((n, d), xf.dtype).at[tok].add(
        gathered * slot_w[:, None].astype(xf.dtype)
    )
    f = jnp.zeros(e, jnp.float32).at[flat_e].add(1.0) / (n * k)
    return out, f, probs.mean(0)


@jax.named_scope("mlp")
def moe_apply_expert_parallel(cfg: ArchConfig, p: dict, x, ctx: ShardCtx):
    """§Perf iteration A1 (beyond-paper): two-stage MoE dispatch.

    The baseline einsum/scatter dispatch lets GSPMD materialize the global
    (E, C, d) buffer on every rank (TB-scale all-reduces — see EXPERIMENTS.md
    §Perf pair A). Here each (data, model) device dispatches its own token
    slice locally and a single tiled all-to-all over the scale-up domain
    routes slots to the expert owners; tokens return on the reverse
    all-to-all and an all-gather rebuilds the TP-replicated activations.
    Capacity is per (rank, expert) — the standard TPU MoE semantics.
    """
    from jax.sharding import PartitionSpec

    from jax import shard_map

    m = cfg.moe
    mesh, tp = ctx.mesh, ctx.tp
    b, s, d = x.shape
    tp_size = mesh.shape[tp]
    dp_size = 1
    for a in ctx.dp:
        dp_size *= mesh.shape[a]
    n_rep = (b // dp_size) * s              # tokens per replica
    if b % dp_size:
        out, aux = moe_apply(cfg, p, x, ctx)     # fallback
        return out, aux
    # §Perf D1: decode has fewer tokens/replica than TP ranks — pad tokens
    # up to a multiple of tp so the all-to-all path applies there too (the
    # einsum fallback read ~170× the activated-expert weight floor).
    n_pad = (-n_rep) % tp_size
    n_tot = n_rep + n_pad
    n_loc = n_tot // tp_size
    cap = int(max(1, round(n_loc * m.top_k / m.n_experts * m.capacity_factor) + (1 if n_pad else 0)))

    expert_keys = [k_ for k_ in ("w_up", "w_gate", "w_down") if k_ in p]

    def body(xl, router, *expert_ws):
        p_local = dict(zip(expert_keys, expert_ws))
        p_local["router"] = router
        bl = xl.shape[0]
        xf = xl.reshape(bl * s, d)
        if n_pad:
            xf = jnp.concatenate(
                [xf, jnp.zeros((n_pad, d), xf.dtype)], axis=0
            )
        r = jax.lax.axis_index(tp)
        mine = jax.lax.dynamic_slice_in_dim(xf, r * n_loc, n_loc, axis=0)
        out, f, pmean = _local_dispatch_compute(cfg, p_local, mine, cap)
        full = jax.lax.all_gather(out, tp, axis=0, tiled=True)  # (n_tot, d)
        full = full[:n_rep]
        f = jax.lax.pmean(f, (tp,) + tuple(ctx.dp))
        pmean = jax.lax.pmean(pmean, (tp,) + tuple(ctx.dp))
        aux = m.n_experts * jnp.sum(f * pmean) * m.router_aux_coef
        return full.reshape(bl, s, d), aux

    P_ = PartitionSpec
    in_specs = [P_(ctx.dp, None, None), P_(None, None)] + [
        P_(tp, None, None) for _ in expert_keys
    ]
    fn = shard_map(
        body, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P_(ctx.dp, None, None), P_()),
        check_vma=False,
    )
    out, aux = fn(x, p["router"], *[p[k_] for k_ in expert_keys])

    if m.shared_expert:
        out = out + mlp_apply(cfg, p["shared"], x, ctx)
    if m.dense_residual:
        out = out + mlp_apply(cfg, p["dense"], x, ctx)
    return ctx.batch(out), {"moe_aux_loss": aux}


def moe_apply_dense_ref(cfg: ArchConfig, p: dict, x, ctx: ShardCtx):
    """O(E·N) oracle: every expert processes every token, masked combine.
    Used by tests to validate the sort-based dispatch (ignoring capacity
    drops, so tests use capacity_factor high enough that nothing drops)."""
    m = cfg.moe
    b, s, d = x.shape
    act = act_fn(cfg.ffn_act)
    xf = x.reshape(-1, d)
    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), p["router"])
    idx, wts, _ = _route(m, logits)
    h = jnp.einsum("nd,edf->enf", xf, p["w_up"])
    if cfg.ffn_gated:
        h = act(jnp.einsum("nd,edf->enf", xf, p["w_gate"])) * h
    else:
        h = act(h)
    y = jnp.einsum("enf,efd->end", h, p["w_down"])  # (E,N,d)
    onehot = jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32)  # (N,k,E)
    w_e = (onehot * wts[..., None]).sum(1)  # (N,E)
    out = jnp.einsum("end,ne->nd", y.astype(jnp.float32), w_e).astype(x.dtype)
    out = out.reshape(b, s, d)
    if m.shared_expert:
        out = out + mlp_apply(cfg, p["shared"], x, ctx)
    if m.dense_residual:
        out = out + mlp_apply(cfg, p["dense"], x, ctx)
    return out
