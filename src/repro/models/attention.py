"""Self/cross attention with GQA, sliding-window / chunked-local masks,
logit softcap, qk-norm, RoPE and KV-cache decode. The softmax core is the
Pallas flash kernel on the TPU where `xla_reason` admits the call, else
naive, or a memory-safe blockwise ("flash", pure-jnp double-scan) path for
long sequences.

Megatron-TP layout (paper §3.1 "Attention blocks"): W_Q/W_O partitioned on the
head dimension over the `model` axis; W_K/W_V replicated whenever
n_kv_heads < TP (every assigned arch) — each rank recomputes the KV heads it
needs, exactly Megatron's GQA behaviour.
"""
from __future__ import annotations

import os as _os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import telemetry
from repro.configs.base import ArchConfig
from repro.kernels.flash_attention import block_sizes, flash_attention
from repro.models.common import ShardCtx, apply_rope, dense_init, rms_norm, softcap

NEG_INF = -2.0e38  # fp32-safe mask value

# Off the TPU, a self-attention core longer than FLASH_SEQ_THRESHOLD runs
# blockwise in jnp (`_attend_flash`, O(block²) live memory), shorter ones
# naive (S² scores). On the TPU the Pallas flash kernel
# (kernels/flash_attention.py) takes every call that `xla_reason` admits:
# it keeps its tiles in VMEM and skips masked blocks. In a chip sweep of
# the core alone (TPU v5e, PERF.md section 6) it beat the naive path at
# every length swept, 512 to 4096, so it takes every length its tiles
# divide (`block_sizes`: multiples of 512).
FLASH_SEQ_THRESHOLD = 8192
FLASH_BLOCK_Q = 512
FLASH_BLOCK_K = 1024
KERNEL_MASKS = {"attn": "causal", "attn_sw": "sliding",
                "attn_chunked": "chunked", "attn_bidir": "bidir"}


# ---------------------------------------------------------------------------
# params

def attn_init(cfg: ArchConfig, key, dtype, *, cross: bool = False) -> dict:
    d, nh, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, nh * hd), d, dtype),
        "wk": dense_init(ks[1], (d, kvh * hd), d, dtype),
        "wv": dense_init(ks[2], (d, kvh * hd), d, dtype),
        "wo": dense_init(ks[3], (nh * hd, d), nh * hd, dtype),
    }
    if cfg.attn_bias and not cross:
        p["bq"] = jnp.zeros((nh * hd,), dtype)
        p["bk"] = jnp.zeros((kvh * hd,), dtype)
        p["bv"] = jnp.zeros((kvh * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attn_specs(cfg: ArchConfig, tp: str = "model", *, cross: bool = False) -> dict:
    s = {
        "wq": P(None, tp),
        "wk": P(None, None),  # kv_heads < TP: replicated (Megatron GQA)
        "wv": P(None, None),
        "wo": P(tp, None),
    }
    if cfg.attn_bias and not cross:
        s.update(bq=P(tp), bk=P(None), bv=P(None))
    if cfg.qk_norm:
        s.update(q_norm=P(None), k_norm=P(None))
    return s


# ---------------------------------------------------------------------------
# masks

def _mask_bias(kind: str, q_pos, k_pos, window: int, chunk: int):
    """Additive mask bias (..., Sq, Sk) from position vectors."""
    q = q_pos[..., :, None]
    k = k_pos[..., None, :]
    if kind == "attn_bidir":
        ok = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), bool)
    else:
        ok = k <= q
        if kind == "attn_sw":
            ok &= k > q - window
        elif kind == "attn_chunked":
            ok &= (k // chunk) == (q // chunk)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# cores

def _group(q, kvh):
    """(B,S,nh,hd) -> (B,kvh,qpk,S,hd)."""
    b, s, nh, hd = q.shape
    return q.reshape(b, s, kvh, nh // kvh, hd).transpose(0, 2, 3, 1, 4)


def _attend_naive(q, k, v, bias, cap: Optional[float]):
    """q: (B,kvh,g,Sq,hd); k/v: (B,Sk,kvh,hd); bias: broadcastable (...,Sq,Sk)."""
    hd = q.shape[-1]
    scores = jnp.einsum(
        "bkgqh,bskh->bkgqs", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * (hd ** -0.5)
    scores = softcap(scores, cap) + bias
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bkgqh", probs, v.astype(jnp.float32))
    return out


def _attend_flash(q, k, v, q_pos, k_pos, kind, window, chunk, cap):
    """Blockwise two-level-scan attention; O(block²) live memory.

    q: (B,kvh,g,Sq,hd) fp32-upcast inside; returns (B,kvh,g,Sq,hd) fp32.

    §Perf C1': K/V/Q blocks are fed to scan/map as PRE-SPLIT xs (not
    dynamic_slice'd inside the body) — the transpose of a scan-carried
    dynamic_slice accumulates cotangents through full-size buffer adds every
    step, which cost more HBM traffic than the naive O(S²) path at 4k.
    With xs, scan's native per-slice cotangent stacking applies.
    """
    b, kvh, g, sq, hd = q.shape
    sk = k.shape[1]
    bq, bk = min(FLASH_BLOCK_Q, sq), min(FLASH_BLOCK_K, sk)
    nq, nk = sq // bq, sk // bk
    assert sq % bq == 0 and sk % bk == 0, (sq, sk)
    scale = hd ** -0.5

    # pre-split blocks: k,v (B,Sk,kvh,hd) -> (nk, B, kvh, bk, hd)
    ks = k.reshape(b, nk, bk, kvh, hd).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(b, nk, bk, kvh, hd).transpose(1, 0, 3, 2, 4)
    kps = k_pos.reshape(nk, bk)
    qs = q.reshape(b, kvh, g, nq, bq, hd).transpose(3, 0, 1, 2, 4, 5) * scale
    qps = q_pos.reshape(nq, bq)

    def q_block(args):
        qi, qp = args  # (B,kvh,g,bq,hd), (bq,)

        def k_step(carry, xs):
            m, l, acc = carry
            kj, vj, kp = xs  # (B,kvh,bk,hd), (B,kvh,bk,hd), (bk,)
            s = jnp.einsum(
                "bkgqh,bksh->bkgqs", qi.astype(jnp.float32), kj.astype(jnp.float32)
            )
            s = softcap(s, cap) if cap is not None else s
            s = s + _mask_bias(kind, qp, kp, window, chunk)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bksh->bkgqh", p, vj.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, bq), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, bq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(k_step, (m0, l0, a0), (ks, vs, kps))
        return acc / jnp.maximum(l, 1e-38)[..., None]

    # remat each q-block so backward recomputes tiles instead of storing them
    q_block = jax.checkpoint(q_block)
    out = jax.lax.map(q_block, (qs, qps))  # (nq,B,kvh,g,bq,hd)
    return out.transpose(1, 2, 3, 0, 4, 5).reshape(b, kvh, g, sq, hd)


# ---------------------------------------------------------------------------
# dispatch

def xla_reason(kind: str, s: int, hd: int, *, cross: bool, cached: bool,
               ctx: ShardCtx, backend: str) -> Optional[str]:
    """Why a call's softmax core stays on the XLA path, or None when the
    Pallas flash kernel takes it. The kernel takes self-attention without a
    cache (training and full forward passes; its masks are by index, and
    such a call's positions are 0..S-1) on the TPU, under no mesh or one
    whose TP axis has size 1 (a head-sharded custom call would be
    all-gathered around), for a mask it has, on whole blocks."""
    if cross:
        return "cross"
    if cached:
        return "cache"
    if backend != "tpu":
        return "backend"
    if ctx.mesh is not None and dict(ctx.mesh.shape).get(ctx.tp, 1) > 1:
        return "tp_sharded"
    if kind not in KERNEL_MASKS:
        return "kind"
    if block_sizes(s, hd) is None:
        return "seq_len"
    return None


# ---------------------------------------------------------------------------
# public apply

@jax.named_scope("attention")
def attn_apply(
    cfg: ArchConfig,
    p: dict,
    x,
    *,
    kind: str,
    ctx: ShardCtx,
    positions=None,          # (S,) absolute positions of x tokens
    kv_x=None,               # cross-attention source (B,T,d); None = self
    cache: Optional[dict] = None,   # {'k','v'} (B,maxT,kvh,hd) + write pos
    cache_pos=None,          # scalar int32: write/valid position for decode
    use_rope: Optional[bool] = None,
    cross_cache: Optional[dict] = None,  # {'ek','ev'} (B,Tenc,kvh,hd): banked
                                         # encoder K/V (fill at prefill when
                                         # kv_x is given, read at decode)
):
    """Returns (out, new_cache)."""
    d, nh, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b, s, _ = x.shape
    use_rope = cfg.use_rope if use_rope is None else use_rope
    cross = kv_x is not None or cross_cache is not None
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)

    q = jnp.einsum("bsd,df->bsf", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(b, s, nh, hd)

    if cross and kv_x is None:
        # decode against the banked encoder K/V: computed (and qk-normed)
        # once at prefill — no per-token encoder pass, no re-norm of k
        k, v = cross_cache["ek"], cross_cache["ev"]
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    else:
        src = kv_x if cross else x
        k = jnp.einsum("bsd,df->bsf", src, p["wk"])
        v = jnp.einsum("bsd,df->bsf", src, p["wv"])
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        k = k.reshape(b, src.shape[1], kvh, hd)
        v = v.reshape(b, src.shape[1], kvh, hd)

        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        if use_rope and not cross:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    q = ctx.heads(q)

    new_cache = None
    if cache is not None and not cross:
        # decode/prefill against a persistent cache.
        # §Perf B1: dynamic_update_slice on a sequence-SHARDED cache triggers
        # XLA's "involuntary full rematerialization" (whole cache gathered +
        # rescattered per step); a scatter with explicit indices partitions
        # shard-locally. REPRO_BASELINE_CACHE=1 restores the DUS path.
        # §Perf B2: attn_sw/attn_chunked caches are ring buffers of
        # window/chunk length (init_kv_cache) — slot i holds absolute
        # position last-((last-i) mod w); unwritten slots mask out as p<0.
        w = cache["k"].shape[1]
        ring = kind in ("attn_sw", "attn_chunked") and not _os.environ.get(
            "REPRO_BASELINE_RINGCACHE"
        )
        last = cache_pos + s - 1

        kc = k.astype(cache["k"].dtype)
        vc = v.astype(cache["v"].dtype)
        if s > w:  # prefill longer than the ring: only the tail survives
            kc, vc = kc[:, -w:], vc[:, -w:]
            write_pos, n_write = cache_pos + s - w, w
        else:
            write_pos, n_write = cache_pos, s
        if _os.environ.get("REPRO_BASELINE_CACHE") or (s > 1 and not ring):
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], kc, write_pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], vc, write_pos, axis=1)
        else:
            idx = write_pos + jnp.arange(n_write, dtype=jnp.int32)
            if ring:
                idx = idx % w
            ck = cache["k"].at[:, idx].set(kc)
            cv = cache["v"].at[:, idx].set(vc)
        new_cache = {"k": ck, "v": cv}

        if s > 1:
            # prefill: attend the fresh full-sequence K/V (the ring stores
            # only the tail; early queries still need their full window)
            k_posm = positions
        else:
            k, v = ck, cv
            slot = jnp.arange(w, dtype=jnp.int32)
            if ring:
                k_pos = last - ((last - slot) % w)
                k_posm = jnp.where(k_pos >= 0, k_pos, jnp.iinfo(jnp.int32).max)
            else:
                k_posm = jnp.where(slot <= last, slot, jnp.iinfo(jnp.int32).max)
    else:
        k_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
        k_posm = k_pos
        if cross and cross_cache is not None:
            if kv_x is not None:
                # prefill: bank the encoder K/V for cache-driven decode
                new_cache = {"ek": k.astype(cross_cache["ek"].dtype),
                             "ev": v.astype(cross_cache["ev"].dtype)}
            else:
                new_cache = cross_cache

    reason = xla_reason(kind, s, hd, cross=cross, cached=cache is not None,
                        ctx=ctx, backend=jax.default_backend())
    tel = telemetry.get()
    if tel.enabled:
        if reason is None:
            tel.counter("attention.path", path="kernel")
        else:
            tel.counter("attention.path", path="xla", reason=reason)

    qg = _group(q, kvh)  # (B,kvh,g,S,hd)

    if reason is None:
        # f32 operands: the kernel's dots round them to bf16 at the default
        # precision as the XLA einsums below do; its output is f32
        f32 = jnp.float32
        out = flash_attention(
            q.transpose(0, 2, 1, 3).astype(f32),
            k.transpose(0, 2, 1, 3).astype(f32),
            v.transpose(0, 2, 1, 3).astype(f32),
            kind=KERNEL_MASKS[kind], window=cfg.window, chunk=cfg.chunk_size,
            softcap=cfg.attn_softcap,
        ).reshape(qg.shape)
    elif cross:
        bias = jnp.zeros((1, 1, 1, s, k.shape[1]), jnp.float32)
        out = _attend_naive(qg, k, v, bias, cfg.attn_softcap)
    elif s > FLASH_SEQ_THRESHOLD:
        # long PREFILL: blockwise. (Decode s==1 stays naive: a 1×T score row
        # is tiny even at T=512k, and XLA turns the softmax reductions over a
        # seq-sharded cache into the psum-LSE combine == flash-decode.)
        out = _attend_flash(
            qg, k, v, positions, k_posm, kind, cfg.window, cfg.chunk_size,
            cfg.attn_softcap,
        )
    else:
        bias = _mask_bias(kind, positions, k_posm, cfg.window, cfg.chunk_size)
        out = _attend_naive(qg, k, v, bias[None, None, None], cfg.attn_softcap)

    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, nh * hd).astype(x.dtype)
    y = jnp.einsum("bsf,fd->bsd", out, p["wo"])
    return ctx.batch(y), new_cache


def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int, dtype,
                  kind: str = "attn") -> dict:
    """§Perf iteration B2 (beyond-paper): sliding-window / chunked-local
    layers only ever attend the trailing window/chunk, so their cache is a
    RING BUFFER of that length (serving-standard, à la Mistral) — for gemma2
    long_500k this cuts 21 of 42 layers' per-token KV reads 128×.
    REPRO_BASELINE_RINGCACHE=1 restores full-length caches."""
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    length = max_len
    if not _os.environ.get("REPRO_BASELINE_RINGCACHE"):
        if kind == "attn_sw":
            length = min(max_len, cfg.window)
        elif kind == "attn_chunked":
            length = min(max_len, cfg.chunk_size)
    return {
        "k": jnp.zeros((batch, length, kvh, hd), dtype),
        "v": jnp.zeros((batch, length, kvh, hd), dtype),
    }


def init_cross_kv_cache(cfg: ArchConfig, batch: int, dtype) -> dict:
    """Encoder K/V bank for enc-dec decode (whisper-style serving): filled
    ONCE at prefill from the encoder output, read by every cross-attention
    decode step — the decoder never re-runs the encoder per token. Fixed
    ``enc_seq`` length (no ring: cross attention is bidirectional over the
    whole encoded input)."""
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    t = cfg.encoder.enc_seq
    return {"ek": jnp.zeros((batch, t, kvh, hd), dtype),
            "ev": jnp.zeros((batch, t, kvh, hd), dtype)}
