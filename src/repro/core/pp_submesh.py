"""Measured multi-mesh pipeline parallelism: the staged train step lowered
onto per-stage submeshes (DESIGN.md §2.8).

`core.ntp_train._make_staged_train_step` EMULATES nonuniform PP: every rank
plays each stage in turn, the stage hand-off is a no-op data dependency, and
the 1F1B bubble is charged analytically by `core.perf_model`. This module is
the real thing on a ``("stage", "data", "model")`` mesh
(`launch.mesh.make_staged_mesh`): stage ``s``'s layer weights live ONLY on
the stage-``s`` device slice, the boundary activation is handed to stage
``s+1`` by `jax.lax.ppermute` over the ``stage`` axis, and the pipeline
schedule is executed tick by tick — ``microbatches + pp - 1`` ticks per
step, every stage computing one microbatch per tick (warmup/drain ticks idle
the edges, which IS the bubble; autodiff through the ppermute runs the
reverse pipeline for the backward). Bubble, overlap, and cross-stage
transfer bytes are therefore MEASURED quantities (`launch.profile
--measure`, `benchmarks.bench_hotpath`); the analytic
`perf_model.staged_iteration_time` number survives as the cross-check.

Geometry: per-stage unit buffers are padded to the widest stage's buffer
(pad slots hold zeros — algebraically inert, the same invariant the
single-mesh packing relies on) and stages owning fewer layers pad with
all-zero layers (exact identities: the residual stream passes through a
zero-weight block unchanged). The stacked tree is built INSIDE the jitted
step from the session's canonical packed tree, so transitions, checkpoints
and the stage-local gradient sync are byte-for-byte the ones the emulated
path uses — `make_ntp_train_step` dispatches here purely on the mesh.
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core import nonuniform as nu
from repro.core import ntp_train as nt
from repro.optim.base import Optimizer, sgd

STAGE_AXES = ("stage", "data", "model")

_ATTN_KEYS = ("wq", "wk", "wv", "wo")


def is_staged_mesh(mesh) -> bool:
    """True for a mesh carrying a ``stage`` axis with >= 2 stages (the
    submesh execution path); the 2-axis ``(data, model)`` mesh — or a
    degenerate stage axis of size 1 — takes the stage-sequential emulation."""
    if mesh is None:
        return False
    names = getattr(mesh, "axis_names", ())
    return "stage" in names and mesh.shape["stage"] > 1


def validate_staged_mesh(mesh, pp: int) -> None:
    names = tuple(getattr(mesh, "axis_names", ()))
    if tuple(sorted(names)) != tuple(sorted(STAGE_AXES)):
        raise ValueError(
            f"submesh PP needs mesh axes {STAGE_AXES}, got {names} "
            "(build one with launch.mesh.make_staged_mesh)"
        )
    if mesh.shape["stage"] != pp:
        raise ValueError(
            f"mesh stage axis has {mesh.shape['stage']} stages but the plan "
            f"has pp={pp}; one submesh per pipeline stage is the contract"
        )


def _stage_geometry(cfg: nt.NTPModelConfig, staged: nu.StagedPlan):
    """Static packing geometry shared by the stacker and the step: per-stage
    layer ids, per-stage weight plans, padded layer count and per-kind
    padded buffer widths (max over stages)."""
    from repro.configs.shapes import stage_boundaries

    bounds = stage_boundaries(cfg.n_layers, staged.pp)
    stage_layers = [tuple(range(bounds[s], bounds[s + 1]))
                    for s in range(staged.pp)]
    l_max = max(len(ls) for ls in stage_layers)
    plans = [nt._plans(cfg, p) for p in staged.stages]
    u_max = {
        kind: max(sp[kind].comp_slots.shape[2] for sp in plans)
        for kind in ("attn", "mlp")
    }
    return stage_layers, l_max, plans, u_max


def _pad_unit_leaf(w, u_have: int, u_want: int):
    """(D, n1*u_have, *unit) -> (D, n1*u_want, *unit): zero pad slots laid
    per model rank, so the padded leaf still splits evenly over ``model``."""
    if u_have == u_want:
        return w
    d, cols = w.shape[0], w.shape[1]
    n1 = cols // u_have
    r = w.reshape(d, n1, u_have, *w.shape[2:])
    pad = [(0, 0), (0, 0), (0, u_want - u_have)] + [(0, 0)] * (w.ndim - 2)
    return jnp.pad(r, pad).reshape(d, n1 * u_want, *w.shape[2:])


def stack_staged_params(cfg: nt.NTPModelConfig, packed, staged: nu.StagedPlan):
    """Packed per-layer tree -> stage-stacked tree + shard_map in_specs.

    Unit leaves become ``(pp, l_max, D, n1*u_max, *unit)`` sharded
    ``P("stage", None, "data", "model")`` — each stage's device slice holds
    exactly its own layers. Replicated layer leaves (ln1/ln2/router) become
    ``(pp, l_max, ...)`` kept REPLICATED (``P()``): the step body
    dynamic-indexes its own stage's row by ``axis_index("stage")``. (They are
    small, and sharding them ``P("stage")`` tripped a partitioner bug on jax
    0.4.x when this stacking was traced inside the same jit as the shard_map
    — the stage slices arrived corrupted; the 4-axis unit spec is
    unaffected. Not re-checked on the installed jax 0.9.)
    Pure jnp reshape/pad/stack — differentiable, so the step's grads flow
    straight back to the packed tree this was built from (pad-slot and
    pad-layer cotangents are dropped by the transpose of the pad)."""
    stage_layers, l_max, plans, u_max = _stage_geometry(cfg, staged)

    rep_keys = ["ln1", "ln2"] + (["router"] if cfg.is_moe else [])
    unit, rep = {}, {}
    for key in nt.UNIT_KEYS:
        kind = "attn" if key in _ATTN_KEYS else "mlp"
        per_stage = []
        for s, layers in enumerate(stage_layers):
            u_s = plans[s][kind].comp_slots.shape[2]
            stk = jnp.stack([
                _pad_unit_leaf(packed["layers"][li][key], u_s, u_max[kind])
                for li in layers
            ])
            if stk.shape[0] < l_max:
                pad = [(0, l_max - stk.shape[0])] + [(0, 0)] * (stk.ndim - 1)
                stk = jnp.pad(stk, pad)
            per_stage.append(stk)
        unit[key] = jnp.stack(per_stage)
    for key in rep_keys:
        per_stage = []
        for layers in stage_layers:
            stk = jnp.stack([packed["layers"][li][key] for li in layers])
            if stk.shape[0] < l_max:
                pad = [(0, l_max - stk.shape[0])] + [(0, 0)] * (stk.ndim - 1)
                stk = jnp.pad(stk, pad)
            per_stage.append(stk)
        rep[key] = jnp.stack(per_stage)

    stacked = {
        "embed": packed["embed"],
        "head": packed["head"],
        "final_norm": packed["final_norm"],
        "unit": unit,
        "rep": rep,
    }
    specs = {
        "embed": P(),
        "head": P(),
        "final_norm": P(),
        "unit": {k: P("stage", None, "data", "model") for k in unit},
        "rep": {k: P() for k in rep},
    }
    return stacked, specs


def handoff_accounting(cfg: nt.NTPModelConfig, staged: nu.StagedPlan, *,
                       local_batch: int, microbatches: int, seq_len: int):
    """Static ledger of one step's cross-stage activation traffic: what the
    ppermute hand-off moves, counted from the actual transfer shapes (the
    backward pipeline moves the same volume of cotangents in reverse).
    tests/dist/session_submesh_pp.py checks this table against the submesh
    step's recorded attribute; `bench_hotpath` records it next to the
    reshard transition ledger in BENCH_train.json."""
    pp, d = staged.pp, staged.d
    mb = local_batch // microbatches
    ticks = microbatches + pp - 1
    act_bytes = 4 * mb * seq_len * cfg.d_model        # f32 (mb, S, d_model)
    senders = (pp - 1) * d * staged.n1                # rank columns that send
    sends = ticks - 1                                 # no send on final tick
    fwd = act_bytes * senders * sends
    return {
        "act_bytes_per_send": act_bytes,
        "sender_ranks": senders,
        "ticks": ticks,
        "sends_per_boundary": sends,
        "fwd_bytes": fwd,
        "bwd_bytes": fwd,                              # ppermute transpose
        "total_bytes": 2 * fwd,
    }


def make_submesh_train_step(
    cfg: nt.NTPModelConfig,
    staged: nu.StagedPlan,
    mesh,
    *,
    mode: Union[nt.Mode, str] = nt.Mode.NTP,
    local_batch: int = 4,
    optimizer: Optional[Optimizer] = None,
    local_batches=None,
    microbatches: int = 1,
    overlap: bool = False,
):
    """The measured twin of `_make_staged_train_step` on a staged mesh.

    Same contract — ``step(params, opt_state, batch) -> (params, opt_state,
    metrics)`` on the session's PACKED trees — and the same stage-local
    gradient sync and optimizer update; only the loss graph differs: the
    forward/backward is the real tick-scheduled pipeline over the ``stage``
    axis. pp=2 output matches the emulated step to f32 tolerance
    (tests/dist/session_submesh_pp.py). The returned step carries
    ``step.ticks``, ``step.submesh`` and ``step.handoff_for(seq_len)`` (the
    cross-stage byte table; ``step.handoff`` binds on first call).

    ``overlap=True`` keeps the pipeline schedule and swaps the per-leaf
    sync for the BUCKETED one (core/overlap, DESIGN.md §2.10): one fused
    collective per (stage, plan-kind) instead of one per (layer, leaf).
    The data-axis sync collectives then sit in the same program region as
    the backward drain ticks, where XLA's scheduler can hide them behind
    the stages that are still draining (healthy buckets stay bit-identical
    to the sequential sync; degraded ones are exact to f32 reassociation)."""
    from repro.configs.shapes import layer_stages

    validate_staged_mesh(mesh, staged.pp)
    mode = nt.Mode.coerce(mode)
    optimizer = optimizer or sgd(1e-2)
    pp, d_axis = staged.pp, staged.d
    _, l_max, stage_plans, u_max = _stage_geometry(cfg, staged)
    stage_of = layer_stages(cfg.n_layers, pp)
    eff = staged.effective

    if not 1 <= microbatches <= local_batch:
        raise ValueError(
            f"microbatches={microbatches} outside [1, local_batch={local_batch}]"
        )
    if local_batch % microbatches:
        raise ValueError(
            f"local_batch={local_batch} not divisible by "
            f"microbatches={microbatches}"
        )
    lb = nt._validated_local_batches(local_batches, eff, mode, local_batch,
                                     d_axis)
    lb_table = jnp.asarray(lb, jnp.int32)
    m = microbatches
    ticks = m + pp - 1

    # MoE: per-stage slot ids, padded to u_max with -1 (the masked pad id)
    if cfg.is_moe:
        tables = []
        for sp in stage_plans:
            slots = np.asarray(sp["mlp"].comp_slots)       # (D, n1, U_s)
            u_s = slots.shape[2]
            if u_s < u_max["mlp"]:
                slots = np.pad(slots, [(0, 0), (0, 0),
                                       (0, u_max["mlp"] - u_s)],
                               constant_values=-1)
            tables.append(slots)
        moe_slots = jnp.asarray(np.stack(tables), jnp.int32)  # (pp, D, n1, U)
    else:
        moe_slots = None

    def global_loss(params, batch):
        """Scalar full-batch mean loss, computed by the real pipeline (AD
        outside the shard_map, exactly as the emulated builders)."""
        stacked, specs = stack_staged_params(cfg, params, staged)

        def body(p_local, tokens_local):
            s = jax.lax.axis_index("stage")
            dd = jax.lax.axis_index("data")
            rr = jax.lax.axis_index("model")
            # my stage's layer stack: (l_max, U, *unit) / (l_max, ...) —
            # unit leaves arrive pre-sliced by the "stage" spec; rep leaves
            # arrive replicated and pick their stage row here
            uw = {k: v.reshape(v.shape[1], *v.shape[3:])
                  for k, v in p_local["unit"].items()}
            rw = {k: v[s] for k, v in p_local["rep"].items()}
            uids = moe_slots[s, dd, rr] if moe_slots is not None else None

            def my_layers(x):
                for l in range(l_max):
                    lp = {k: v[l] for k, v in uw.items()}
                    lp.update({k: v[l] for k, v in rw.items()})
                    x = x + nt._attn_local(lp, nt._rms(x, lp["ln1"]), cfg)
                    if cfg.is_moe:
                        x = x + nt._moe_local(lp, nt._rms(x, lp["ln2"]),
                                              uids, cfg)
                    else:
                        x = x + nt._mlp_local(lp, nt._rms(x, lp["ln2"]))
                return x

            mb = tokens_local.shape[0] // m
            seq = tokens_local.shape[1] - 1
            is_first = (s == 0)
            is_last = (s == pp - 1)
            recv = jnp.zeros((mb, seq, cfg.d_model), jnp.float32)
            total = jnp.float32(0.0)
            count = jnp.float32(0.0)
            for t in range(ticks):
                j0 = min(t, m - 1)          # stage 0's microbatch this tick
                toks0 = tokens_local[j0 * mb:(j0 + 1) * mb]
                emb = p_local["embed"][toks0[:, :-1]]
                x = jnp.where(is_first, emb, recv)
                y = my_layers(x)
                jl = t - (pp - 1)           # the microbatch draining at the
                if 0 <= jl < m:             # last stage this tick (static)
                    toksl = tokens_local[jl * mb:(jl + 1) * mb]
                    tgt = toksl[:, 1:]
                    mask = (
                        (jl * mb + jnp.arange(mb)) < lb_table[dd]
                    ).astype(jnp.float32)
                    logits = jnp.einsum(
                        "bsd,dv->bsv", nt._rms(y, p_local["final_norm"]),
                        p_local["head"],
                    ).astype(jnp.float32)
                    lse = jax.nn.logsumexp(logits, axis=-1)
                    ll = jnp.take_along_axis(
                        logits, tgt[..., None], axis=-1)[..., 0]
                    tok_loss = (lse - ll) * mask[:, None]
                    total = total + jnp.where(is_last, tok_loss.sum(), 0.0)
                    count = count + jnp.where(
                        is_last,
                        (mask[:, None] * jnp.ones_like(tok_loss)).sum(), 0.0,
                    )
                if t < ticks - 1:
                    # hand the boundary activation to the next stage; the
                    # last stage has no outgoing edge and stage 0 receives
                    # zeros it never reads
                    recv = jax.lax.ppermute(
                        y, "stage", [(i, i + 1) for i in range(pp - 1)]
                    )
            total = jax.lax.psum(total, ("stage", "data"))
            count = jax.lax.psum(count, ("stage", "data"))
            return total / jnp.maximum(count, 1.0)

        return shard_map(
            body, mesh=mesh, in_specs=(specs, P("data", None)),
            out_specs=P(), check_vma=False,
        )(stacked, batch)

    # Stage-local NTP gradient sync (shared body — core/overlap): grads
    # live on the packed per-layer tree (the stacking happened inside the
    # loss and its transpose undid it), so each layer reshards under its
    # OWN stage's plan over the ``data`` axis. ``overlap`` swaps in the
    # bucketed route: one fused collective per (stage, plan-kind).
    from repro.core import overlap as ov

    sync_grads = ov.make_sync_grads(cfg, staged, mesh, mode=mode,
                                    bucketed=overlap)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def _step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(global_loss)(params, batch)
        grads = sync_grads(grads)
        with jax.named_scope("optimizer"):
            new_params, new_state, metrics = optimizer.update(
                grads, opt_state, params,
                norm_weights=nt._norm_weights(grads, d_axis),
            )
        metrics = dict(metrics, loss=loss)
        return new_params, new_state, metrics

    def step(params, opt_state, batch):
        if step.handoff is None:
            # seq len arrives with the first batch; bind the byte table then
            step.handoff = handoff_accounting(
                cfg, staged, local_batch=local_batch, microbatches=m,
                seq_len=batch.shape[1] - 1,
            )
        return _step(params, opt_state, batch)

    step.ticks = ticks
    step.submesh = True
    step.handoff = None
    step.handoff_for = lambda seq_len: handoff_accounting(
        cfg, staged, local_batch=local_batch, microbatches=m,
        seq_len=seq_len,
    )
    step.overlap = overlap
    step.collectives = sync_grads.collectives
    step.grads_fn = jax.jit(jax.value_and_grad(global_loss))
    step.sync_fn = jax.jit(sync_grads)
    return step
