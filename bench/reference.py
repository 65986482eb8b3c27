"""Plain pieces the configurations' references are built from: float32
``jax.numpy`` with no kernel, cache, sharding or code of the program under
test. The caller sets the matmul precision the configuration states
(``jax.default_matmul_precision``); the control passes ``dtype=bfloat16``
and computes in the precision below.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp

Q_BLOCK = 1024   # queries per block of the attention scores (memory bound)


def rms_norm(x, g, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta: float):
    """Rotary embedding over positions 0..S-1, the half-split convention of
    the Llama and Granite families. x: (B, S, H, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def causal_attention(q, k, v, scale: float):
    """Grouped-query causal attention. q: (B, S, KV, G, hd); k, v: (B, S, KV,
    hd). Scores in float32, one block of queries at a time so that the S x S
    scores of a long sequence never live whole."""
    b, s, kvh, g, hd = q.shape
    blk = s if s <= 2 * Q_BLOCK else Q_BLOCK
    assert s % blk == 0, (s, blk)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        sc = jnp.einsum("bqkgh,bskh->bkgqs", qi.astype(jnp.float32), kf) * scale
        qpos = i * blk + jnp.arange(blk)
        mask = jnp.arange(s)[None, :] <= qpos[:, None]
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bkgqs,bskh->bqkgh", p, vf)

    out = jax.lax.map(jax.checkpoint(one), jnp.arange(s // blk))
    # (n_blk, B, blk, KV, G, hd) -> (B, S, KV, G, hd)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, kvh, g, hd)
    return out.astype(q.dtype)


def cross_entropy(logits, targets, row_weight=None):
    """Mean next-token cross-entropy in float32 over the rows that count
    (``row_weight`` 1) and all their positions."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    tok = lse - ll
    if row_weight is None:
        return jnp.mean(tok)
    w = row_weight[:, None] * jnp.ones_like(tok)
    return jnp.sum(tok * w) / jnp.sum(w)


def warmup_cosine(step, warmup: int, total: int, floor: float):
    """Linear warm-up from 0, then cosine decay to ``floor``: the multiplier
    on the learning rate at optimizer step ``step`` (0 for the first)."""
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / max(warmup, 1), 1.0)
    prog = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
    return warm * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))


def global_norm(tree) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def adamw(opt: Dict, lr_scale_fn: Callable = None):
    """AdamW with decoupled weight decay on every leaf and gradient clipping
    by the global norm (Loshchilov & Hutter; the clip as in Megatron).
    Returns ``step(params, state, grads, t) -> params, state, clipped`` where
    ``t`` counts optimizer steps from 0 and ``clipped`` are the gradients
    as the moments take them."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr0, wd, clip_at = opt["lr"], opt["weight_decay"], opt["grad_clip"]

    def step(params, state, grads, t):
        gn = global_norm(grads)
        clip = jnp.minimum(1.0, clip_at / jnp.maximum(gn, 1e-9))
        g = jax.tree.map(lambda x: x.astype(jnp.float32) * clip, grads)
        m = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, state["m"], g)
        v = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, state["v"], g)
        n = t + 1
        bc1, bc2 = 1 - b1 ** n, 1 - b2 ** n
        lr = lr0 * (lr_scale_fn(t) if lr_scale_fn is not None else 1.0)
        new = jax.tree.map(
            lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)
                                      + wd * p),
            params, m, v)
        return new, {"m": m, "v": v}, g

    return step


def adamw_init(params):
    z = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"m": jax.tree.map(z, params), "v": jax.tree.map(z, params)}


def named_norms(tree, prefix: str = "") -> Dict[str, jnp.ndarray]:
    """{dotted leaf name: L2 norm} of a nested dict/list of arrays."""
    out = {}

    def walk(node, name):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], f"{name}.{k}" if name else str(k))
        elif isinstance(node, (list, tuple)):
            for i, x in enumerate(node):
                walk(x, f"{name}.{i}" if name else str(i))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(node.astype(jnp.float32))))

    walk(tree, prefix)
    return out
