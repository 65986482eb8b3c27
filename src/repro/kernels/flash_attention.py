"""Flash attention with a backward pass — Pallas TPU kernels.

Built on JAX's splash attention kernels
(`jax.experimental.pallas.ops.tpu.splash_attention`): a forward kernel and
a fused backward kernel (dk, dv and per-kv-block dq partials, summed in
f32) under one ``custom_vjp``, with

  - the running max, the denominator and the output accumulator in VMEM
    scratch (f32), and the logsumexp saved for the backward (f32);
  - block-sparse masks: a (q block, kv block) tile that the mask empties is
    neither loaded nor multiplied (causal, sliding-window and chunked masks
    skip every tile wholly above the diagonal or outside the window);
  - GQA: each KV head is index-mapped to its ``H // KVH`` query heads, never
    repeated in HBM.

Precision is the caller's dtype. With f32 q/k/v every MXU dot runs at the
default precision, which on the TPU rounds each operand (q, k, v, p, dO,
dS) to bfloat16 once and accumulates in f32 — the rounding an XLA
default-precision f32 einsum makes; the softmax statistics, accumulators,
the output and dq/dk/dv stay f32.

Tiling: `block_sizes(s, d)` fixes the tiles of both kernels from the
sequence length and head width (TPU v5e sweep, PERF.md section 6).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.experimental.pallas.ops.tpu import splash_attention as splash
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_mask as mask_lib,
)

from repro.kernels.mode import pallas_interpret

KINDS = ("causal", "sliding", "chunked", "bidir")


def block_sizes(s: int, d: int) -> Optional[tuple[int, int]]:
    """(forward tile, backward tile) for sequence length ``s`` and head
    width ``d``, square q x kv tiles; None where 512 does not divide ``s``.
    From TPU v5e sweeps at hd 64 (PERF.md section 6): 1024-tiles are
    the fastest forward from S 1024 up. The fused backward alone ran
    faster on 1024-tiles, but the training step did not, so it keeps 512.
    Both compile at hd 64 and 128."""
    del d
    if s % 512:
        return None
    return (1024 if s % 1024 == 0 else 512), 512


def _mask(kind: str, s: int, window: int, chunk: int):
    shape = (s, s)
    if kind == "causal":
        return splash.CausalMask(shape)
    if kind == "sliding":  # k in (q - window, q]
        return splash.LocalMask(shape, window_size=(window - 1, 0), offset=0)
    if kind == "chunked":
        return mask_lib.ChunkedCausalMask(shape, chunk_size=chunk)
    if kind == "bidir":
        return splash.FullMask(shape)
    raise ValueError(f"flash_attention: unknown kind={kind!r}; one of {KINDS}")


def flash_attention(
    q, k, v, *,
    kind: str = "causal",          # causal | sliding | chunked | bidir
    window: int = 4096,
    chunk: int = 8192,
    softcap: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool | None = None,
):
    """q: (B, H, S, D); k/v: (B, KVH, S, D) with H % KVH == 0.
    Returns softmax(q k^T / sqrt(D)) v as (B, H, S, D) in q.dtype;
    differentiable in q, k and v.

    Tiles default to `block_sizes(S, D)`, else to the whole sequence;
    ``block_q``/``block_k`` set both kernels' tiles.
    ``interpret=None`` resolves via
    `kernels.mode.pallas_interpret` (compiled on TPU, interpret on CPU)."""
    b, h, s, d = q.shape
    fwd, bwd = block_sizes(s, d) or (s, s)
    bq = fwd if block_q is None else min(block_q, s)
    bk = fwd if block_k is None else min(block_k, s)
    bq_bwd = bwd if block_q is None else bq
    bk_bwd = bwd if block_k is None else bk
    for size, what, arg in ((bq, "query-block", "block_q"),
                            (bk, "key-block", "block_k")):
        if s % size:
            raise ValueError(
                f"flash_attention: sequence length s={s} is not divisible by "
                f"the {what} size {arg}={size}; pad the sequence or pass a "
                f"{arg} that divides {s}"
            )
    interpret = pallas_interpret(interpret)

    mask = splash.MultiHeadMask([_mask(kind, s, window, chunk)] * h)
    kernel = splash.make_splash_mha(
        mask,
        block_sizes=splash.BlockSizes(
            block_q=bq, block_kv=bk, block_kv_compute=bk,
            block_q_dkv=bq_bwd, block_kv_dkv=bk_bwd,
            block_kv_dkv_compute=bk_bwd, use_fused_bwd_kernel=True,
        ),
        attn_logits_soft_cap=softcap,
        head_shards=1, q_seq_shards=1, interpret=interpret,
    )
    return jax.vmap(kernel)(q * d ** -0.5, k, v)
