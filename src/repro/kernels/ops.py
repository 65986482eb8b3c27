"""Jit'd public wrappers for the Pallas kernels.

Execution mode is resolved PER CALL by `pallas_interpret`
(`kernels/mode.py`): compiled wherever a non-CPU device exists, interpret
on CPU; ``interpret=True`` forces interpret mode. Each mode jit-caches
separately (``interpret`` is a static argname).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import bucket as _bk
from repro.kernels import flash_attention as _fa
from repro.kernels import reshard_pack as _rp
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd
from repro.kernels.mode import pallas_interpret


@functools.partial(
    jax.jit, static_argnames=("kind", "window", "chunk", "softcap",
                              "block_q", "block_k", "interpret")
)
def _flash_attention(q, k, v, *, kind, window, chunk, softcap, block_q,
                     block_k, interpret):
    return _fa.flash_attention(
        q, k, v, kind=kind, window=window, chunk=chunk, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


def flash_attention(q, k, v, *, kind="causal", window=4096, chunk=8192,
                    softcap=None, block_q=None, block_k=None, interpret=None):
    return _flash_attention(
        q, k, v, kind=kind, window=window, chunk=chunk, softcap=softcap,
        block_q=block_q, block_k=block_k,
        interpret=pallas_interpret(interpret, kernel="flash_attention"),
    )


@functools.partial(
    jax.jit, static_argnames=("eps", "plus_one", "block_rows", "interpret")
)
def _rmsnorm(x, w, *, eps, plus_one, block_rows, interpret):
    return _rn.rmsnorm(
        x, w, eps=eps, plus_one=plus_one, block_rows=block_rows,
        interpret=interpret,
    )


def rmsnorm(x, w, *, eps=1e-6, plus_one=False, block_rows=256,
            interpret=None):
    return _rmsnorm(
        x, w, eps=eps, plus_one=plus_one, block_rows=block_rows,
        interpret=pallas_interpret(interpret, kernel="rmsnorm"),
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_scan(x, dt, A, B, C, *, chunk, interpret):
    return _ssd.ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=interpret)


def ssd_scan(x, dt, A, B, C, *, chunk=256, interpret=None):
    return _ssd_scan(
        x, dt, A, B, C, chunk=chunk,
        interpret=pallas_interpret(interpret, kernel="ssd_scan"),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _reshard_pack(src, send_idx, *, interpret):
    return _rp.reshard_pack(src, send_idx, interpret=interpret)


def reshard_pack(src, send_idx, *, interpret=None):
    return _reshard_pack(
        src, send_idx,
        interpret=pallas_interpret(interpret, kernel="reshard_pack"),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bucket_pack(leaves, *, interpret):
    return _bk.bucket_pack(leaves, interpret=interpret)


def bucket_pack(leaves, *, interpret=None):
    """Fuse same-row 2-D gradient leaves into one flat bucket
    (kernels/bucket.py — the overlapped-sync copy engine)."""
    return _bucket_pack(
        tuple(leaves),
        interpret=pallas_interpret(interpret, kernel="bucket_pack"),
    )


@functools.partial(jax.jit, static_argnames=("widths", "interpret"))
def _bucket_unpack(flat, *, widths, interpret):
    return _bk.bucket_unpack(flat, widths, interpret=interpret)


def bucket_unpack(flat, widths, *, interpret=None):
    """Split a packed bucket back into per-leaf arrays (inverse of
    `bucket_pack`, same static column offsets)."""
    return _bucket_unpack(
        flat, widths=tuple(int(w) for w in widths),
        interpret=pallas_interpret(interpret, kernel="bucket_unpack"),
    )
