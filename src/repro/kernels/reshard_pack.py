"""NTP reshard send-bucket packing — Pallas TPU kernel.

The inner loop of the paper's pre/post-sync reshard (§4.1): gather partition
units from the local comp/sync buffer into per-destination all-to-all send
buckets according to the static Algorithm-1 tables. On GPU this is the
torch.split + all_to_all prep in Fig. 12; on TPU we fuse the gather so the
send buffer is produced in one VMEM pass.

  grid = (n_dst, s_max, row_blocks); the (n_dst·s_max,) index table is
  scalar-prefetched into SMEM and picks, per grid step, which source unit
  row the pipeline DMAs into VMEM (index U = the zero pad row).

Each unit row is viewed as (rows, 128) lanes — unit rows are 128-element
multiples by construction (DESIGN.md §3.2); other widths are zero-padded to
one — and copied in row blocks of at most ``_BLOCK_BYTES``, so no VMEM
block grows with the source or the unit size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import pallas_interpret

_LANES = 128
_BLOCK_BYTES = 1 << 20


def _pack_kernel(idx_ref, src_ref, out_ref):
    del idx_ref  # consumed by the index maps
    out_ref[...] = src_ref[...]


def _row_block(rows: int, itemsize: int) -> int:
    """Largest sublane-aligned divisor of ``rows`` within the block budget
    (or ``rows`` itself, a legal full-extent block)."""
    cap = max(8, _BLOCK_BYTES // (_LANES * itemsize))
    if rows <= cap:
        return rows
    for rb in range(cap - cap % 8, 7, -8):
        if rows % rb == 0:
            return rb
    return rows


def reshard_pack(src, send_idx, *, interpret: bool | None = None):
    """src: (U+1, unit_elems) — zero-padded unit buffer (last row zeros).
    send_idx: (n, s_max) int32 local slot per (dst, msg-slot), pad = U.
    Returns send buffer (n, s_max, unit_elems).

    ``interpret=None`` resolves via `kernels.mode.pallas_interpret`
    (compiled on TPU/GPU, interpret on CPU)."""
    up1, elems = src.shape
    n, s_max = send_idx.shape
    interpret = pallas_interpret(interpret)
    padded = -(-elems // _LANES) * _LANES
    if padded != elems:
        src = jnp.pad(src, ((0, 0), (0, padded - elems)))
    rows = padded // _LANES
    rb = _row_block(rows, src.dtype.itemsize)
    out = pl.pallas_call(
        _pack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, s_max, rows // rb),
            in_specs=[pl.BlockSpec(
                (None, rb, _LANES),
                lambda i, s, r, idx: (idx[i * s_max + s], r, 0))],
            out_specs=pl.BlockSpec(
                (None, None, rb, _LANES), lambda i, s, r, idx: (i, s, r, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, s_max, rows, _LANES), src.dtype),
        interpret=interpret,
    )(send_idx.reshape(-1).astype(jnp.int32), src.reshape(up1, rows, _LANES))
    return out.reshape(n, s_max, padded)[..., :elems]
