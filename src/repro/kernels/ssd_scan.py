"""Mamba-2 SSD chunk scan — Pallas TPU kernel.

The compute core of mamba2-780m: per (batch, head), iterate chunks
sequentially (innermost grid dim), carrying the (hp, ds) SSD state in VMEM
scratch; within a chunk use the matmul-heavy dual form (decay-masked
C Bᵀ attention-like block plus state injection) — MXU-aligned with
chunk length L=256, hp=64, ds=128 tiles.

  grid = (B·NH, S/L)
  A  whole (B·NH,) vector in SMEM (one scalar per grid row)
  x  tile (L, hp)   dt tile (1, L)   B,C tiles (L, ds)
  y  tile (L, hp)   state scratch (hp, ds) fp32

``dt`` enters as a (B·NH, 1, S) row view, whose (1, L) tile is legal on the
TPU (the unit dim equals the array's). Its column form, the within-chunk
cumulative sums and their transposes are masked reductions over the
(L, L) tile — lane and sublane sums, no relayout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import pallas_interpret

# the state recurrence is fp32: at the TPU's default precision an f32
# matmul runs as one bf16 pass, which loses ~1e-2 against the exact scan
_F32 = jax.lax.Precision.HIGHEST


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, h_ref, *, L: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[pl.program_id(0)]                    # scalar A (negative)
    x = x_ref[...].astype(jnp.float32)             # (L, hp)
    dt_row = dt_ref[...].astype(jnp.float32)       # (1, L)
    B = b_ref[...].astype(jnp.float32)             # (L, ds)
    C = c_ref[...].astype(jnp.float32)             # (L, ds)

    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    lower = ii >= jj
    diag = ii == jj
    dt_col = jnp.sum(jnp.where(diag, dt_row, 0.0), axis=1, keepdims=True)
    # inclusive cumsum of da = dt·a (≤ 0), as a column and as a row
    acum_col = jnp.sum(jnp.where(lower, dt_row * a, 0.0), axis=1,
                       keepdims=True)              # (L, 1)
    acum_row = jnp.sum(jnp.where(diag, acum_col, 0.0), axis=0,
                       keepdims=True)              # (1, L)
    atot = jnp.sum(dt_row) * a

    # intra-chunk dual form
    G = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                            precision=_F32, preferred_element_type=jnp.float32)  # (L, L)
    decay = jnp.exp(jnp.clip(acum_col - acum_row, -60.0, 0.0))
    M = jnp.where(lower, G * decay * dt_row, 0.0)
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            precision=_F32, preferred_element_type=jnp.float32)  # (L, hp)

    # inter-chunk: contribution of the carried state
    h = h_ref[...]                                 # (hp, ds)
    cdec = jnp.exp(jnp.clip(acum_col, -60.0, 0.0)) * C           # (L, ds)
    y = y + jax.lax.dot_general(cdec, h, (((1,), (1,)), ((), ())),
                                precision=_F32, preferred_element_type=jnp.float32)

    # state update: h' = exp(atot) h + sum_j exp(atot - acum_j) dt_j x_j B_j^T
    w = jnp.exp(jnp.clip(atot - acum_col, -60.0, 0.0)) * dt_col  # (L, 1)
    inj = jax.lax.dot_general(x * w, B, (((0,), (0,)), ((), ())),
                              precision=_F32, preferred_element_type=jnp.float32)  # (hp, ds)
    h_ref[...] = jnp.exp(atot) * h + inj

    y_ref[...] = y.astype(y_ref.dtype)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 256,
             interpret: bool | None = None):
    """SSD scan over (BH, S, ·) flattened batch·heads.

    x: (BH, S, hp); dt: (BH, S); A: (BH,); B, C: (BH, S, ds).
    Returns y: (BH, S, hp) fp32. (Zero initial state; the recurrent decode
    path lives in models/ssm.py — this kernel is the train/prefill hot loop.)

    ``interpret=None`` resolves via `kernels.mode.pallas_interpret`
    (compiled on TPU/GPU, interpret on CPU).
    """
    bh, s, hp = x.shape
    ds = B.shape[-1]
    L = min(chunk, s)
    if s % L != 0:
        raise ValueError(
            f"ssd_scan: sequence length s={s} is not divisible by the "
            f"chunk length chunk={L}; pad the sequence or pass a chunk "
            f"that divides {s}"
        )
    interpret = pallas_interpret(interpret)
    kernel = functools.partial(_ssd_kernel, L=L)
    return pl.pallas_call(
        kernel,
        grid=(bh, s // L),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, L, hp), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, 1, L), lambda b, c: (b, 0, c)),
            pl.BlockSpec((None, L, ds), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, L, ds), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((None, L, hp), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hp, ds), jnp.float32)],
        interpret=interpret,
    )(A, x, dt[:, None, :], B, C)
