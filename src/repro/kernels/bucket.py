"""Gradient-bucket pack/unpack — Pallas TPU kernels (DESIGN.md §2.10).

The overlapped gradient sync (`core.overlap`) fuses every leaf that shares a
reshard plan into ONE flat (rows, Σwidths) buffer before the collective, so
the NTP reshard→psum→reshard path issues one collective per (bucket, stage)
instead of one per leaf. This module is the copy engine for that fusion: the
send-bucket gather of `kernels/reshard_pack` generalized from "rows of one
source by index" to "column slices of many sources at static offsets" —
each leaf's flattened payload lands in its own contiguous column range of
the bucket, in one VMEM pass:

  pack:   (rows, w_0), ..., (rows, w_{k-1})  ->  (rows, w_0+...+w_{k-1})
  unpack: the exact inverse (the same offsets, read instead of written).

Row count is shared by construction — bucketed leaves share a `WeightPlan`,
whose tables index unit ROWS only, so the concatenated payload is opaque to
the Algorithm-1 tables and the fused buffer reshards with the per-leaf
tables unchanged. Offsets and widths are static (they come from the leaf
shapes), so both kernels are straight-line copies with no index traffic.

Tiling: the grid runs over column tiles of the bucket, ``t`` lanes wide
(a 128-multiple that divides every leaf width), so no tile straddles two
leaves and no VMEM block grows with the bucket. Every operand holds a
double-buffered block, so ``t`` is sized for all of them together to stay
inside ``_VMEM_BYTES`` (below the 16 MiB default scoped-VMEM limit). Leaf
``i`` owns tiles ``[start_i, start_i + n_i)``; its block index is clamped
into that range, so outside it the pipeline neither refetches its input nor
writes back its output. Widths that are not 128-multiples (small replicated
leaves) fall back to one whole-array block.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mode import pallas_interpret

_LANES = 128
_VMEM_BYTES = 12 << 20


def _col_tile(rows: int, widths: Tuple[int, ...], itemsize: int):
    """Column tile (lanes) shared by every leaf, or None when the widths
    admit no 128-aligned common tile."""
    g = math.gcd(*widths)
    if g % _LANES:
        return None
    block_bytes = _VMEM_BYTES // (2 * (len(widths) + 1))
    t = _LANES * max(1, min(g // _LANES,
                            block_bytes // (rows * itemsize * _LANES)))
    while g % t:
        t -= _LANES
    return t


def _tile_ranges(widths, t):
    """(start tile, tile count) of each leaf in the bucket."""
    out, start = [], 0
    for w in widths:
        out.append((start, w // t))
        start += w // t
    return out


def _leaf_block(rows, t, start, count):
    return pl.BlockSpec(
        (rows, t),
        lambda j: (0, jnp.minimum(jnp.maximum(j - start, 0), count - 1)))


def _pack_kernel(*refs, ranges):
    """refs = (*leaf_refs, out_ref): copy the leaf owning this tile."""
    out_ref, j = refs[-1], pl.program_id(0)
    for ref, (start, count) in zip(refs[:-1], ranges):
        @pl.when((j >= start) & (j < start + count))
        def _copy(ref=ref):
            out_ref[...] = ref[...]


def _unpack_kernel(flat_ref, *out_refs, ranges):
    j = pl.program_id(0)
    for ref, (start, count) in zip(out_refs, ranges):
        @pl.when((j >= start) & (j < start + count))
        def _copy(ref=ref):
            ref[...] = flat_ref[...]


def _pack_whole(*refs):
    out_ref = refs[-1]
    off = 0
    for ref in refs[:-1]:
        w = ref.shape[1]
        out_ref[:, off:off + w] = ref[...]
        off += w


def _unpack_whole(flat_ref, *out_refs):
    off = 0
    for ref in out_refs:
        w = ref.shape[1]
        ref[...] = flat_ref[:, off:off + w]
        off += w


def bucket_pack(leaves: Sequence, *, interpret: bool | None = None):
    """Fuse 2-D leaves ``(rows, w_i)`` (same rows, same dtype) into one
    ``(rows, sum(w_i))`` bucket. A single leaf passes through unchanged (no
    kernel launch — nothing to fuse).

    ``interpret=None`` resolves via `kernels.mode.pallas_interpret`
    (compiled on TPU/GPU, interpret on CPU)."""
    leaves = tuple(leaves)
    if not leaves:
        raise ValueError("bucket_pack needs at least one leaf")
    rows = leaves[0].shape[0]
    dtype = leaves[0].dtype
    for x in leaves:
        if x.ndim != 2 or x.shape[0] != rows or x.dtype != dtype:
            raise ValueError(
                f"bucket leaves must be 2-D (rows={rows}, w) of {dtype}; got "
                f"{[(tuple(l.shape), str(l.dtype)) for l in leaves]}"
            )
    if len(leaves) == 1:
        return leaves[0]
    widths = tuple(x.shape[1] for x in leaves)
    total = sum(widths)
    interpret = pallas_interpret(interpret)
    out_shape = jax.ShapeDtypeStruct((rows, total), dtype)
    t = _col_tile(rows, widths, jnp.dtype(dtype).itemsize)
    if t is None:
        return pl.pallas_call(
            _pack_whole,
            grid=(1,),
            in_specs=[pl.BlockSpec(x.shape, lambda i: (0, 0))
                      for x in leaves],
            out_specs=pl.BlockSpec((rows, total), lambda i: (0, 0)),
            out_shape=out_shape,
            interpret=interpret,
        )(*leaves)
    ranges = _tile_ranges(widths, t)
    return pl.pallas_call(
        functools.partial(_pack_kernel, ranges=ranges),
        grid=(total // t,),
        in_specs=[_leaf_block(rows, t, *r) for r in ranges],
        out_specs=pl.BlockSpec((rows, t), lambda j: (0, j)),
        out_shape=out_shape,
        interpret=interpret,
    )(*leaves)


def bucket_unpack(flat, widths: Tuple[int, ...], *,
                  interpret: bool | None = None):
    """Split a ``(rows, sum(widths))`` bucket back into per-leaf ``(rows,
    w_i)`` arrays — the exact inverse of `bucket_pack` (same static
    offsets). Returns a tuple, one array per width."""
    widths = tuple(int(w) for w in widths)
    rows, total = flat.shape
    if sum(widths) != total:
        raise ValueError(f"widths {widths} do not sum to {total}")
    if len(widths) == 1:
        return (flat,)
    interpret = pallas_interpret(interpret)
    out_shape = [jax.ShapeDtypeStruct((rows, w), flat.dtype) for w in widths]
    t = _col_tile(rows, widths, flat.dtype.itemsize)
    if t is None:
        return tuple(pl.pallas_call(
            _unpack_whole,
            grid=(1,),
            in_specs=[pl.BlockSpec((rows, total), lambda i: (0, 0))],
            out_specs=[pl.BlockSpec((rows, w), lambda i: (0, 0))
                       for w in widths],
            out_shape=out_shape,
            interpret=interpret,
        )(flat))
    ranges = _tile_ranges(widths, t)
    return tuple(pl.pallas_call(
        functools.partial(_unpack_kernel, ranges=ranges),
        grid=(total // t,),
        in_specs=[pl.BlockSpec((rows, t), lambda j: (0, j))],
        out_specs=[_leaf_block(rows, t, *r) for r in ranges],
        out_shape=out_shape,
        interpret=interpret,
    )(flat))


def bucket_pack_ref(leaves: Sequence):
    """jnp oracle for `bucket_pack` (concatenate along columns) — the parity
    baseline in tests/test_overlap.py."""
    leaves = tuple(leaves)
    return leaves[0] if len(leaves) == 1 else jnp.concatenate(leaves, axis=1)


def bucket_unpack_ref(flat, widths: Tuple[int, ...]):
    """jnp oracle for `bucket_unpack` (static column slices)."""
    out, off = [], 0
    for w in widths:
        out.append(flat[:, off:off + w])
        off += w
    return tuple(out)
