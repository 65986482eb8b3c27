"""Overlapped, bucketed gradient synchronization (DESIGN.md §2.10).

Every step builder in `core.ntp_train` runs the full backward, then
synchronizes ALL gradients serially — per-leaf reshard → psum('data') →
reshard — so DP sync time sits fully on the critical path. This module makes
the sync overlapped, bucketed, and shared:

* `make_sync_grads` is the ONE sync body the three step builders
  (`make_ntp_train_step`, `_make_staged_train_step`,
  `pp_submesh.make_submesh_train_step`) previously each carried a copy of.
  With ``bucketed=False`` it reproduces the per-leaf reshard→psum→reshard
  route bit-identically (a pp=1 `StagedPlan` degenerates to the uniform
  body: ``as_staged(plan).stages[0] is plan``). With ``bucketed=True`` every
  leaf that shares a (stage, WeightPlan) is fused into one flat buffer by
  the Pallas pack/unpack kernels (`kernels/bucket.py`) before the
  collective: one collective per (bucket, stage) instead of one per leaf.
  The fused buffer reshards under the per-leaf Algorithm-1 tables UNCHANGED
  — the tables index unit rows only, and column-concatenation commutes with
  the row gather/scatter and the elementwise psum, so the bucketed sync is
  bit-identical to the sequential one on healthy stages and exact to f32
  reassociation on degraded ones (tests/test_overlap.py proves both against
  the numpy reshard twin).

* `make_overlapped_train_step` is the AD-inside-shard_map twin of
  `make_ntp_train_step`: the backward is layer-chunked on the
  `stage_boundaries` ladder and each chunk's bucketed sync is ISSUED as
  soon as its grads exist, while the previous chunk's backward runs — a
  one-chunk-deep in-flight pipeline (issue chunk L, complete chunk L+1).
  On accelerators XLA's latency-hiding scheduler turns that program order
  into real comm/compute overlap; on the CPU emulation collectives are
  synchronous, so the measured win is the collective-count collapse — both
  are captured by `perf_model.overlap_iteration_time` (exposed_comm =
  max(0, sync − overlappable_compute), with a zero overlappable window on
  the emulation).

Gradient-correctness note (the reason the repo's builders keep AD OUTSIDE
shard_map): seeding a cotangent on every rank over-counts replicated paths,
because jax transposes ``psum`` to ``psum`` (the all-ones matrix is
symmetric). The overlapped builder seeds ``ct/n1`` per model rank instead;
the psum('model') transposes then restore the FULL cotangent on every
rank-local value (sum of n1 equal shares), unit-leaf grads come out exactly
as the AD-outside path's pre-sync grads (so psum('data') completes them),
and replicated-leaf grads sum to the true gradient under an explicit psum
over ('data', 'model'). Verified against the AD-outside step in
tests/test_overlap.py and the dist lifecycle runs.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.configs.shapes import layer_stages, stage_boundaries
from repro.core import nonuniform as nu
from repro.core import ntp_train as nt
from repro.core import reshard as rs
from repro.kernels import ops
from repro.optim.base import Optimizer, sgd

_ATTN_KEYS = ("wq", "wk", "wv", "wo")
_REP_AXES = ("data", "model")

# pp=1 backward chunk ladder: enough chunks to pipeline sync behind
# backward, few enough that each bucket stays collective-worthy
DEFAULT_CHUNKS = 4


def coerce_overlap(v) -> bool:
    """CLI/config coercion: accepts bools and 'on'/'off' (+truthy spellings)."""
    if isinstance(v, bool):
        return v
    s = str(v).strip().lower()
    if s in ("on", "true", "1", "yes"):
        return True
    if s in ("off", "false", "0", "no"):
        return False
    raise ValueError(f"overlap must be on/off, got {v!r}")


def _layer_idx(path):
    # params["layers"][i][key] paths carry the layer index one hop up
    for e in reversed(path):
        if hasattr(e, "idx"):
            return e.idx
    return None


def chunk_ranges(n_layers: int, pp: int) -> Tuple[Tuple[int, int], ...]:
    """The backward chunk ladder: the stage boundaries at pp>1 (a chunk must
    never straddle stages — each bucket syncs under ONE stage plan), up to
    `DEFAULT_CHUNKS` even chunks at pp=1."""
    n = pp if pp > 1 else min(n_layers, DEFAULT_CHUNKS)
    b = stage_boundaries(n_layers, n)
    return tuple((b[i], b[i + 1]) for i in range(n) if b[i + 1] > b[i])


@dataclass(frozen=True)
class Bucket:
    """One fused sync group: every leaf in ``leaves`` shares ``stage``'s
    ``kind`` WeightPlan, so their row-aligned flats concatenate into one
    collective payload."""

    stage: int
    kind: str                            # "attn" | "mlp"
    leaves: Tuple[Tuple[int, str], ...]  # ((layer, key), ...)


def bucket_layout(cfg, staged, chunks=None) -> Tuple[Bucket, ...]:
    """One bucket per (chunk, plan-kind), chunk ladder defaulting to the
    stages, in REVERSED chunk order: the backward produces the last chunk's
    grads first, so its buckets issue first."""
    staged = nu.as_staged(staged)
    stage_of = layer_stages(cfg.n_layers, staged.pp)
    if chunks is None:
        chunks = chunk_ranges(cfg.n_layers, staged.pp) if staged.pp > 1 else \
            ((0, cfg.n_layers),)
    out = []
    for lo, hi in reversed(tuple(chunks)):
        s = stage_of[lo]
        assert all(stage_of[l] == s for l in range(lo, hi)), \
            f"chunk [{lo},{hi}) straddles stages {stage_of}"
        for kind, keys in (("attn", _ATTN_KEYS), ("mlp", ("A", "B"))):
            out.append(Bucket(s, kind,
                              tuple((l, k) for l in range(lo, hi)
                                    for k in keys)))
    return tuple(out)


def sync_collectives(cfg, staged, mode, *, bucketed: bool,
                     chunks=None) -> int:
    """Static count of collective launches one gradient sync performs — the
    quantity bucketing collapses. A degraded sync is reshard → psum →
    reshard = 3 launches (all_to_all + psum + all_to_all); a healthy one is
    a single psum. Replicated leaves are free on the AD-outside path (the
    shard_map transpose carries them) and one fused psum per chunk plus the
    tail/embed psums on the overlapped path — those are counted by
    `make_overlapped_train_step` itself."""
    staged = nu.as_staged(staged)
    mode = nt.Mode.coerce(mode)
    stage_of = layer_stages(cfg.n_layers, staged.pp)

    def cost(stage):
        degraded = mode is nt.Mode.NTP and not staged.stages[stage].healthy
        return 3 if degraded else 1

    if bucketed:
        return sum(cost(b.stage) for b in bucket_layout(cfg, staged, chunks))
    return sum(cost(stage_of[l]) * len(nt.UNIT_KEYS)
               for l in range(cfg.n_layers))


def _bucket_syncers(staged, stage_plans, mode):
    """(issue, complete) closures over one staged plan. ``issue`` packs a
    bucket's squeezed (u, *unit) leaf grads into one (u, ΣE) flat and — on a
    degraded stage — launches the pre-sync reshard (the first collective of
    the Algorithm-1 chain); ``complete`` runs the psum('data') (+ post
    reshard) and unpacks. The split is what the overlapped backward
    interleaves: issue chunk L while chunk L-1's backward runs."""

    def issue(bucket: Bucket, arrs):
        shapes = tuple(a.shape for a in arrs)
        flats = [a.reshape(a.shape[0], -1) for a in arrs]
        widths = tuple(f.shape[1] for f in flats)
        flat = ops.bucket_pack(flats)
        splan = staged.stages[bucket.stage]
        degraded = mode is nt.Mode.NTP and not splan.healthy
        if degraded:
            wp = stage_plans[bucket.stage][bucket.kind]
            flat = rs.reshard(flat.reshape(flat.shape[0], 1, -1), wp.pre)
        return (bucket, flat, widths, shapes, degraded)

    def complete(state):
        bucket, flat, widths, shapes, degraded = state
        flat = jax.lax.psum(flat, "data")
        if degraded:
            wp = stage_plans[bucket.stage][bucket.kind]
            flat = rs.reshard(flat, wp.post)
            flat = flat.reshape(flat.shape[0], -1)
        parts = ops.bucket_unpack(flat, widths)
        return [p.reshape(s) for p, s in zip(parts, shapes)]

    return issue, complete


def make_sync_grads(cfg, staged, mesh, *, mode, bucketed: bool = False):
    """The ONE gradient-sync body shared by every step builder (DESIGN.md
    §2.10): stage-local NTP sync on the packed per-layer grads tree — each
    layer's unit grads reshard → psum('data') → reshard under its OWN
    stage's plan; a healthy stage takes the plain psum fast path even while
    another stage is degraded (no cross-stage traffic — the sync collective
    never mixes stages). Replicated leaves pass through: on the AD-outside
    path the shard_map transpose already summed every rank's contribution.

    ``bucketed=False`` is the sequential per-leaf oracle, bit-identical to
    the bodies it replaced. ``bucketed=True`` fuses each (stage, plan-kind)
    group into one flat payload via `kernels/bucket.py` before the
    collective. The returned callable carries ``.collectives`` (static
    launch count) and ``.bucketed``."""
    staged = nu.as_staged(staged)
    mode = nt.Mode.coerce(mode)
    stage_of = layer_stages(cfg.n_layers, staged.pp)
    stage_plans = [nt._plans(cfg, p) for p in staged.stages]

    if not bucketed:
        def sync_grads(grads):
            specs = nt._tree_specs(grads)

            def body(g_local):
                def sync(path, g):
                    key = nt._path_key(path)
                    if key not in nt.UNIT_KEYS:
                        return g
                    s = stage_of[_layer_idx(path)]
                    sp = stage_plans[s]
                    wp = sp["attn"] if key in _ATTN_KEYS else sp["mlp"]
                    splan = staged.stages[s]
                    g = g.reshape(g.shape[1:])  # drop replica dim
                    orig_shape = g.shape
                    if mode is nt.Mode.NTP and not splan.healthy:
                        g = rs.ntp_sync_gradient(
                            g.reshape(g.shape[0], 1, -1), wp)
                        g = g.reshape(orig_shape)
                    else:
                        g = jax.lax.psum(g, "data")
                    return g.reshape((1,) + g.shape)

                return jax.tree_util.tree_map_with_path(sync, g_local)

            return shard_map(
                body, mesh=mesh, in_specs=(specs,), out_specs=specs,
                check_vma=False,
            )(grads)

        sync_grads.collectives = sync_collectives(cfg, staged, mode,
                                                  bucketed=False)
        sync_grads.bucketed = False
        return sync_grads

    buckets = bucket_layout(cfg, staged)
    issue, complete = _bucket_syncers(staged, stage_plans, mode)

    def sync_grads(grads):
        specs = nt._tree_specs(grads)

        def body(g_local):
            layers = [dict(lp) for lp in g_local["layers"]]
            # issue every bucket (pack + pre-reshard), then complete — the
            # standalone sync has no backward to hide behind, so give XLA
            # the full window of in-flight collectives at once
            states = [
                issue(b, [layers[l][k].reshape(layers[l][k].shape[1:])
                          for l, k in b.leaves])
                for b in buckets
            ]
            for b, st in zip(buckets, states):
                for (l, k), g in zip(b.leaves, complete(st)):
                    layers[l][k] = g.reshape((1,) + g.shape)
            out = dict(g_local)
            out["layers"] = layers
            return out

        return shard_map(
            body, mesh=mesh, in_specs=(specs,), out_specs=specs,
            check_vma=False,
        )(grads)

    sync_grads.collectives = sync_collectives(cfg, staged, mode,
                                              bucketed=True)
    sync_grads.bucketed = True
    return sync_grads


def make_overlapped_train_step(
    cfg,
    fplan,
    mesh,
    *,
    mode=nt.Mode.NTP,
    local_batch: int = 4,
    optimizer: Optional[Optimizer] = None,
    local_batches=None,
    microbatches: int = 1,
):
    """Overlapped twin of `make_ntp_train_step` on the 2-axis (data, model)
    mesh: same contract (``step(params, opt_state, batch) -> (params,
    opt_state, metrics)``), same loss, gradients equal to the AD-outside
    step's to f32 reassociation — but the backward is layer-chunked on the
    `stage_boundaries` ladder with each chunk's bucketed sync issued while
    the next (earlier) chunk's backward runs.

    Differences from the sequential step, all documented in DESIGN.md §2.10:

    * AD runs INSIDE the shard_map (per-rank vjp seeded ``ct/n1``; see the
      module docstring) so sync can interleave with backward compute.
    * ``microbatches`` is validated exactly as the staged builder's but the
      forward runs the full local batch in ONE chunked pass — the sample
      mask is microbatch-invariant, so the loss matches the microbatched
      emulation to f32 summation order.
    * Replicated leaves sync through one fused psum('data','model') bucket
      per chunk (ln1/ln2/router) plus a tail bucket (final_norm + head,
      issued FIRST — the backward's earliest grads — completed last) and
      the embed psum (the backward's last grad).

    The returned step carries probes for the bench/profile paths:
    ``.overlap`` (True), ``.chunks``, ``.collectives`` (static unit-bucket
    launch count), ``.grads_fn`` (jit'd loss+synced-grads, non-donating)
    and ``.sync_fn`` / ``.sync_off_fn`` (standalone bucketed / sequential
    sync of a grads tree, for timing sync in isolation)."""
    staged = nu.as_staged(fplan)
    mode = nt.Mode.coerce(mode)
    optimizer = optimizer or sgd(1e-2)
    pp = staged.pp
    d_axis = staged.d
    n1 = staged.n1
    stage_of = layer_stages(cfg.n_layers, pp)
    stage_plans = [nt._plans(cfg, p) for p in staged.stages]

    if not 1 <= microbatches <= local_batch:
        raise ValueError(
            f"microbatches={microbatches} outside [1, local_batch={local_batch}]"
        )
    if local_batch % microbatches:
        raise ValueError(
            f"local_batch={local_batch} not divisible by "
            f"microbatches={microbatches}"
        )
    lb = nt._validated_local_batches(local_batches, staged.effective, mode,
                                     local_batch, d_axis)
    lb_table = jnp.asarray(lb, jnp.int32)

    chunks = chunk_ranges(cfg.n_layers, pp)
    per_chunk_buckets = []
    for lo, hi in chunks:
        per_chunk_buckets.append(tuple(
            Bucket(stage_of[lo], kind,
                   tuple((l, k) for l in range(lo, hi) for k in keys))
            for kind, keys in (("attn", _ATTN_KEYS), ("mlp", ("A", "B")))
        ))
    issue, complete = _bucket_syncers(staged, stage_plans, mode)

    moe_slots = (
        [jnp.asarray(sp["mlp"].comp_slots, jnp.int32) for sp in stage_plans]
        if cfg.is_moe else None
    )
    rep_keys = ("ln1", "ln2") + (("router",) if cfg.is_moe else ())

    def _pack_rep(arrs):
        """Fuse replicated-leaf grads (any shapes, rows=1 flats) into one
        psum payload; returns in-flight (flat, widths, shapes)."""
        shapes = tuple(a.shape for a in arrs)
        flats = [a.reshape(1, -1) for a in arrs]
        widths = tuple(f.shape[1] for f in flats)
        return (ops.bucket_pack(flats), widths, shapes)

    def _complete_rep(state):
        flat, widths, shapes = state
        flat = jax.lax.psum(flat, _REP_AXES)
        parts = ops.bucket_unpack(flat, widths)
        return [p.reshape(s) for p, s in zip(parts, shapes)]

    def loss_and_grads(params, batch):
        specs = nt._tree_specs(params)

        def body(p_local, tokens_local):
            dd = jax.lax.axis_index("data")
            rr = jax.lax.axis_index("model")
            p_sq = jax.tree_util.tree_map_with_path(nt._squeeze_unit, p_local)
            uids = (
                [moe_slots[s][dd, rr] for s in stage_of]
                if moe_slots is not None else [None] * cfg.n_layers
            )
            B = tokens_local.shape[0]
            inp, tgt = tokens_local[:, :-1], tokens_local[:, 1:]
            mask = (jnp.arange(B) < lb_table[dd]).astype(jnp.float32)

            # ---- forward: embed | chunk_0 … chunk_{C-1} | tail, each under
            # its own vjp so the backward can interleave sync with compute.
            # The chunk bodies are the residual loop of `_forward_totals`,
            # verbatim (same primitives → same math).
            x, vjp_embed = jax.vjp(lambda emb: emb[inp], p_sq["embed"])
            vjps = []
            for lo, hi in chunks:
                cparams = [p_sq["layers"][l] for l in range(lo, hi)]

                def chunk_fn(cps, xx, lo=lo, hi=hi):
                    for l, lp in zip(range(lo, hi), cps):
                        xx = xx + nt._attn_local(lp, nt._rms(xx, lp["ln1"]),
                                                 cfg)
                        if cfg.is_moe:
                            xx = xx + nt._moe_local(
                                lp, nt._rms(xx, lp["ln2"]), uids[l], cfg)
                        else:
                            xx = xx + nt._mlp_local(lp, nt._rms(xx, lp["ln2"]))
                    return xx

                x, vjp = jax.vjp(chunk_fn, cparams, x)
                vjps.append(vjp)

            def tail_fn(fn_w, head_w, xx):
                logits = jnp.einsum("bsd,dv->bsv", nt._rms(xx, fn_w), head_w)
                logits = logits.astype(jnp.float32)
                lse = jax.nn.logsumexp(logits, axis=-1)
                ll = jnp.take_along_axis(logits, tgt[..., None],
                                         axis=-1)[..., 0]
                return ((lse - ll) * mask[:, None]).sum()

            total, vjp_tail = jax.vjp(tail_fn, p_sq["final_norm"],
                                      p_sq["head"], x)
            count = (mask[:, None]
                     * jnp.ones((B, tgt.shape[1]), jnp.float32)).sum()
            total_g = jax.lax.psum(total, "data")
            count_g = jax.lax.psum(count, "data")
            denom = jnp.maximum(count_g, 1.0)
            loss = total_g / denom

            # ---- backward with a one-chunk-deep in-flight sync pipeline
            seed = (1.0 / denom) / n1
            g_fn, g_head, dx = vjp_tail(seed)
            # tail rep bucket: the earliest grads — issue first, complete
            # last (the widest overlap window of the step)
            tail_state = _pack_rep([g_fn, g_head])

            def _issue_chunk(ci, g_layers):
                lo, hi = chunks[ci]
                unit_states = [
                    issue(b, [g_layers[l - lo][k] for l, k in b.leaves])
                    for b in per_chunk_buckets[ci]
                ]
                rep_state = _pack_rep([g_layers[j][k]
                                       for j in range(hi - lo)
                                       for k in rep_keys])
                return (unit_states, rep_state)

            def _complete_chunk(ci, state):
                lo, hi = chunks[ci]
                unit_states, rep_state = state
                out = [dict() for _ in range(hi - lo)]
                for b, st in zip(per_chunk_buckets[ci], unit_states):
                    for (l, k), g in zip(b.leaves, complete(st)):
                        out[l - lo][k] = g
                parts = iter(_complete_rep(rep_state))
                for j in range(hi - lo):
                    for k in rep_keys:
                        out[j][k] = next(parts)
                return out

            synced = [None] * len(chunks)
            pending = None
            for ci in reversed(range(len(chunks))):
                g_layers, dx = vjps[ci](dx)
                if pending is not None:
                    pj, st = pending
                    synced[pj] = _complete_chunk(pj, st)
                pending = (ci, _issue_chunk(ci, g_layers))
            pj, st = pending
            synced[pj] = _complete_chunk(pj, st)
            (g_embed,) = vjp_embed(dx)
            g_embed = jax.lax.psum(g_embed, _REP_AXES)
            g_fn, g_head = _complete_rep(tail_state)

            out_layers = []
            for ci, (lo, hi) in enumerate(chunks):
                for l in range(lo, hi):
                    ld = synced[ci][l - lo]
                    out_layers.append({
                        k: (ld[k].reshape((1,) + ld[k].shape)
                            if k in nt.UNIT_KEYS else ld[k])
                        for k in p_sq["layers"][l]
                    })
            grads = {"embed": g_embed, "head": g_head, "final_norm": g_fn,
                     "layers": out_layers}
            return loss, grads

        return shard_map(
            body, mesh=mesh, in_specs=(specs, P("data", None)),
            out_specs=(P(), specs), check_vma=False,
        )(params, batch)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        loss, grads = loss_and_grads(params, batch)
        with jax.named_scope("optimizer"):
            new_params, new_state, metrics = optimizer.update(
                grads, opt_state, params,
                norm_weights=nt._norm_weights(grads, d_axis),
            )
        metrics = dict(metrics, loss=loss)
        return new_params, new_state, metrics

    step.overlap = True
    step.chunks = chunks
    step.collectives = sync_collectives(cfg, staged, mode, bucketed=True,
                                        chunks=chunks)
    step.grads_fn = jax.jit(loss_and_grads)
    step.sync_fn = jax.jit(
        make_sync_grads(cfg, staged, mesh, mode=mode, bucketed=True))
    step.sync_off_fn = jax.jit(
        make_sync_grads(cfg, staged, mesh, mode=mode, bucketed=False))
    return step
