"""NTP training: transformer layers computed under nonuniform tensor
parallelism inside shard_map, with NTP gradient synchronization.

This is the paper's prototype workload (§5.1: transformer layers, 2+ DP
replicas, one at reduced TP) expressed JAX-natively:

* every TP-sharded weight lives in a padded unit buffer (core/nonuniform.py);
  a degraded replica holds all units on its first n_r ranks, failed ranks
  hold zeros (algebraically inert — DESIGN.md §3.1);
* forward/backward is Megatron-TP: per-unit partial sums + psum('model');
* gradient sync is reshard → psum('data') → reshard (core/reshard.py), the
  paper's pre/post-sync resharding with a 1:1 sync-rank mapping;
* degraded replicas process a reduced local batch (sample masking — the
  paper's local-batch reduction; NTP-PW instead keeps full batch and
  power-boosts, which is modeled analytically in core/power.py).

Also provides the DP-DROP baseline (drop every replica containing a failure)
and a dense single-logical-copy reference for equivalence tests.
"""
from __future__ import annotations

import enum
import functools
from dataclasses import dataclass
from typing import Dict, Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core import nonuniform as nu
from repro.optim.base import Optimizer, sgd


class Mode(enum.Enum):
    """Gradient-synchronization regime of one training job (DESIGN.md §2.2).

    UNIFORM  — every replica healthy; plain DP all-reduce.
    NTP      — nonuniform TP: reshard → psum('data') → reshard sync.
    DP_DROP  — baseline: replicas containing a failure contribute nothing.
    """

    UNIFORM = "uniform"
    NTP = "ntp"
    DP_DROP = "dpdrop"

    @classmethod
    def coerce(cls, v: Union["Mode", str]) -> "Mode":
        if isinstance(v, Mode):
            return v
        return cls(str(v).lower().replace("-", "").replace("_", ""))


@dataclass(frozen=True)
class NTPModelConfig:
    """The prototype transformer (paper §5.1 profiles hidden 6144/12288; we
    default smaller for CPU tests but the structure is identical)."""

    d_model: int = 256
    n_kv_groups: int = 8          # attention partition units
    q_per_kv: int = 2
    head_dim: int = 32
    d_ff: int = 1024
    unit_rows: int = 128          # MLP partition unit (TPU lane-aligned)
    n_layers: int = 2
    vocab: int = 512
    # MoE mode (DESIGN.md §4: the expert is the natural NTP unit — a lost
    # rank's experts are re-placed by Algorithm 1 like head/row units)
    n_experts: int = 0            # 0 = dense MLP
    top_k: int = 2

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def k_ff(self) -> int:
        if self.is_moe:
            return self.n_experts  # partition unit = whole expert
        assert self.d_ff % self.unit_rows == 0
        return self.d_ff // self.unit_rows


# ---------------------------------------------------------------------------
# canonical (dense) params + packing

def init_canonical(cfg: NTPModelConfig, key) -> Dict:
    ks = jax.random.split(key, cfg.n_layers + 1)
    d, g, q, h = cfg.d_model, cfg.n_kv_groups, cfg.q_per_kv, cfg.head_dim

    def layer(k):
        kk = jax.random.split(k, 7)
        s = d ** -0.5
        ffu = cfg.d_ff if cfg.is_moe else cfg.unit_rows  # rows per ffn unit
        p = {
            "ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
            # unit-major layouts: (k_units, ...)
            "wq": jax.random.normal(kk[0], (g, d, q * h)) * s,
            "wk": jax.random.normal(kk[1], (g, d, h)) * s,
            "wv": jax.random.normal(kk[2], (g, d, h)) * s,
            "wo": jax.random.normal(kk[3], (g, q * h, d)) * (q * h) ** -0.5,
            "A": jax.random.normal(kk[4], (cfg.k_ff, d, ffu)) * s,
            "B": jax.random.normal(kk[5], (cfg.k_ff, ffu, d)) * cfg.d_ff ** -0.5,
        }
        if cfg.is_moe:
            p["router"] = jax.random.normal(kk[6], (d, cfg.n_experts)) * s
        return p

    return {
        "embed": jax.random.normal(ks[0], (cfg.vocab, d)) * 0.02,
        "head": jax.random.normal(ks[0], (d, cfg.vocab)) * d ** -0.5,
        "final_norm": jnp.ones((d,), jnp.float32),
        "layers": [layer(ks[i + 1]) for i in range(cfg.n_layers)],
    }


def _plans(cfg: NTPModelConfig, fplan: nu.FailurePlan):
    return {
        "attn": nu.weight_plan(cfg.n_kv_groups, fplan),
        "mlp": nu.weight_plan(cfg.k_ff, fplan),
    }


def _layer_plans(cfg: NTPModelConfig, fplan):
    """Per-layer {attn, mlp} weight plans. A plain `FailurePlan` (pp=1)
    gives every layer the same plan (the `lru_cache`d objects, so this is
    free); a `StagedPlan` gives each layer its OWN stage's plan — stage
    boundaries come from `configs.shapes.stage_boundaries` (DESIGN.md §2.6),
    so a layer's buffers are packed for exactly the scale-up domain that
    computes it."""
    staged = nu.as_staged(fplan)
    if staged.pp == 1:
        return [_plans(cfg, staged.stages[0])] * cfg.n_layers
    from repro.configs.shapes import layer_stages

    per_stage = [_plans(cfg, p) for p in staged.stages]
    return [per_stage[s] for s in layer_stages(cfg.n_layers, staged.pp)]


def _pack_unit(w, wp: nu.WeightPlan):
    """Canonical unit-major weight (k, *unit_shape) -> (D, n1*buf, *unit)."""
    k = w.shape[0]
    flat = np.asarray(w).reshape(k, 1, *w.shape[1:])  # unit dim = 1 group
    return jnp.asarray(nu.pack_global(flat.reshape(k, -1), wp, 1).reshape(
        wp.comp_slots.shape[0], -1, *w.shape[1:]
    ))


def _copy(x):
    # replicated leaves pass through pack/unpack unchanged: materialize a
    # fresh buffer so donated step inputs never alias caller-held trees
    return jnp.array(x, copy=True)


def pack_params(cfg: NTPModelConfig, canonical: Dict, fplan) -> Dict:
    """Canonical -> packed unit buffers under ``fplan`` (a `FailurePlan`, or
    a `StagedPlan` whose stages pack their own layers independently)."""
    lplans = _layer_plans(cfg, fplan)
    out = {
        "embed": _copy(canonical["embed"]),
        "head": _copy(canonical["head"]),
        "final_norm": _copy(canonical["final_norm"]),
        "layers": [],
    }
    for lp, plans in zip(canonical["layers"], lplans):
        out["layers"].append(
            {
                "ln1": _copy(lp["ln1"]),
                "ln2": _copy(lp["ln2"]),
                "wq": _pack_unit(lp["wq"], plans["attn"]),
                "wk": _pack_unit(lp["wk"], plans["attn"]),
                "wv": _pack_unit(lp["wv"], plans["attn"]),
                "wo": _pack_unit(lp["wo"], plans["attn"]),
                "A": _pack_unit(lp["A"], plans["mlp"]),
                "B": _pack_unit(lp["B"], plans["mlp"]),
                **({"router": _copy(lp["router"])} if "router" in lp else {}),
            }
        )
    return out


def unpack_params(cfg: NTPModelConfig, packed: Dict, fplan,
                  replica: int = 0) -> Dict:
    lplans = _layer_plans(cfg, fplan)

    def unp(w, wp):
        arr = np.asarray(w)
        flat = arr.reshape(arr.shape[0], arr.shape[1], 1, -1)  # explicit unit dim
        out = nu.unpack_global(flat, wp, 1, replica).reshape(wp.k, *arr.shape[2:])
        return jnp.asarray(out)

    out = {
        "embed": _copy(packed["embed"]),
        "head": _copy(packed["head"]),
        "final_norm": _copy(packed["final_norm"]),
        "layers": [],
    }
    for lp, plans in zip(packed["layers"], lplans):
        out["layers"].append(
            {
                "ln1": _copy(lp["ln1"]),
                "ln2": _copy(lp["ln2"]),
                "wq": unp(lp["wq"], plans["attn"]),
                "wk": unp(lp["wk"], plans["attn"]),
                "wv": unp(lp["wv"], plans["attn"]),
                "wo": unp(lp["wo"], plans["attn"]),
                "A": unp(lp["A"], plans["mlp"]),
                "B": unp(lp["B"], plans["mlp"]),
                **({"router": _copy(lp["router"])} if "router" in lp else {}),
            }
        )
    return out


def repack_params(cfg: NTPModelConfig, packed: Dict, old: nu.FailurePlan,
                  new: nu.FailurePlan, *, replica: int = 0) -> Dict:
    """Re-express a packed tree under a new failure plan (params or any tree
    mirroring the param structure, e.g. AdamW moments) via the DIRECT
    packed→packed transition (repro.reshard.transition): only units whose
    rank changes move, in one fused bucket per (replica, src, dst) pair —
    the dense ``pack(unpack(...))`` round-trip this replaced survives as the
    test oracle (tests/test_transition_engine.py). ``replica`` is retired
    (the direct route uses every replica's own buffers; after sync they all
    hold the same logical units) and kept only for signature compatibility.
    """
    del replica
    if new == old:
        return packed
    if isinstance(old, nu.StagedPlan) or isinstance(new, nu.StagedPlan):
        from repro.reshard.transition import transition_staged_trees

        (tree,), _ = transition_staged_trees(
            cfg, [packed], nu.as_staged(old), nu.as_staged(new)
        )
        return tree
    from repro.reshard.transition import transition_params

    tree, _ = transition_params(cfg, packed, old, new)
    return tree


# ---------------------------------------------------------------------------
# forward (local math inside shard_map; model_axis=None -> dense reference
# with no collectives — every unit on one logical rank)

def _psum(x, axis):
    return jax.lax.psum(x, axis) if axis is not None else x


@jax.named_scope("norm")
def _rms(x, w):
    v = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(v + 1e-6) * w


@jax.named_scope("attention")
def _attn_local(lp, h, cfg: NTPModelConfig, model_axis="model"):
    """h: (B,S,d) replicated; unit-buffered weights (U, d, ...)."""
    b, s, d = h.shape
    q = jnp.einsum("bsd,udr->bsur", h, lp["wq"])
    k = jnp.einsum("bsd,udh->bsuh", h, lp["wk"])
    v = jnp.einsum("bsd,udh->bsuh", h, lp["wv"])
    u = q.shape[2]
    q = q.reshape(b, s, u, cfg.q_per_kv, cfg.head_dim)
    scores = jnp.einsum("bsugh,btuh->bugst", q, k) * cfg.head_dim ** -0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None, None], scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bugst,btuh->bsugh", probs.astype(h.dtype), v)
    out = out.reshape(b, s, u, cfg.q_per_kv * cfg.head_dim)
    y = jnp.einsum("bsur,urd->bsd", out, lp["wo"])
    return _psum(y, model_axis)


@jax.named_scope("mlp")
def _mlp_local(lp, h, model_axis="model"):
    a = jax.nn.gelu(jnp.einsum("bsd,udf->bsuf", h, lp["A"]))
    z = jnp.einsum("bsuf,ufd->bsd", a, lp["B"])
    return _psum(z, model_axis)


@jax.named_scope("mlp")
def _moe_local(lp, h, unit_ids, cfg: NTPModelConfig, model_axis="model"):
    """NTP-MoE ffn: partition unit = whole expert (DESIGN.md §4). Each rank
    computes its local expert units on all tokens (dense-masked prototype
    formulation), gated by the replicated router; zero-padded units are
    inert (gelu(0)·0) and their gates are masked.

    h: (B,S,d); unit_ids: (U,) global expert id per buffer slot, -1 = pad.
    """
    e, k = cfg.n_experts, cfg.top_k
    logits = jnp.einsum("bsd,de->bse", h, lp["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    gates = (jax.nn.one_hot(idx, e, dtype=jnp.float32) * w[..., None]).sum(-2)

    a = jax.nn.gelu(jnp.einsum("bsd,udf->bsuf", h, lp["A"]))
    y = jnp.einsum("bsuf,ufd->bsud", a, lp["B"])
    gate_u = gates[..., jnp.clip(unit_ids, 0)] * (unit_ids >= 0)
    z = jnp.einsum("bsud,bsu->bsd", y, gate_u.astype(y.dtype))
    return _psum(z, model_axis)


def _forward_totals(cfg: NTPModelConfig, params, tokens, sample_mask,
                    moe_unit_ids=None, model_axis="model"):
    """One forward over all layers; returns the LOCAL (pre-data-psum)
    (token-loss total, token count) pair so callers can accumulate across
    microbatches before normalizing (the stage-sequential 1F1B emulation).

    The layer loop IS the pipeline: layers are visited in stage order and the
    residual stream `x` is the activation handed from stage s to stage s+1
    (in this emulation every rank plays each stage in turn, so the hand-off
    is a no-op data dependency rather than a ppermute; DESIGN.md §2.6).
    ``moe_unit_ids`` is either one (U,) slot-id array shared by every layer
    (uniform plan) or a per-layer sequence (staged plans differ by stage)."""
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    with jax.named_scope("embed"):
        x = params["embed"][inp]
    per_layer = isinstance(moe_unit_ids, (list, tuple))
    for i, lp in enumerate(params["layers"]):
        uids = moe_unit_ids[i] if per_layer else moe_unit_ids
        x = x + _attn_local(lp, _rms(x, lp["ln1"]), cfg, model_axis)
        if cfg.is_moe:
            x = x + _moe_local(lp, _rms(x, lp["ln2"]), uids, cfg, model_axis)
        else:
            x = x + _mlp_local(lp, _rms(x, lp["ln2"]), model_axis)
    with jax.named_scope("loss_head"):
        logits = jnp.einsum("bsd,dv->bsv", _rms(x, params["final_norm"]),
                            params["head"])
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
        tok_loss = (lse - ll) * sample_mask[:, None]
    return tok_loss.sum(), (sample_mask[:, None] * jnp.ones_like(tok_loss)).sum()


def _forward_local(cfg: NTPModelConfig, params, tokens, sample_mask,
                   moe_unit_ids=None, axes=("data", "model")):
    """tokens: (B, S+1) local; sample_mask: (B,) bool. Returns global loss.
    moe_unit_ids: (U,) this rank's global expert id per slot (MoE mode).
    axes=(None, None) runs the dense single-logical-copy reference."""
    data_axis, model_axis = axes
    total, count = _forward_totals(cfg, params, tokens, sample_mask,
                                   moe_unit_ids, model_axis)
    total = _psum(total, data_axis)
    count = _psum(count, data_axis)
    return total / jnp.maximum(count, 1.0)


def make_reference_loss(cfg: NTPModelConfig):
    """Dense single-logical-copy loss on CANONICAL params — no mesh, no
    collectives; the oracle for the NTP equivalence tests.

    loss(canonical_params, tokens (B,S+1), sample_mask (B,)) -> scalar.
    """
    uids = jnp.arange(cfg.k_ff, dtype=jnp.int32) if cfg.is_moe else None

    def loss(canonical, tokens, sample_mask):
        return _forward_local(cfg, canonical, tokens, sample_mask, uids,
                              axes=(None, None))

    return loss


# ---------------------------------------------------------------------------
# train step builder

UNIT_KEYS = ("wq", "wk", "wv", "wo", "A", "B")

_UNIT_SPEC = P("data", "model")
_REP_SPEC = P()


def _path_key(path):
    return path[-1].key if hasattr(path[-1], "key") else None


def _tree_specs(params):
    """shard_map specs: unit buffers split over (data, model), everything
    else replicated. Shared by the uniform and staged step builders."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: (
            _UNIT_SPEC if _path_key(path) in UNIT_KEYS else _REP_SPEC
        ),
        params,
    )


def _squeeze_unit(path, x):
    return x.reshape(x.shape[1:]) if _path_key(path) in UNIT_KEYS else x


def _norm_weights(params, d_axis: int):
    # packed unit buffers hold D identical copies of every synced unit
    # gradient: weight them 1/D so the global grad norm (clipping + the
    # grad_norm metric) equals the canonical-training norm exactly
    return jax.tree_util.tree_map_with_path(
        lambda path, _: 1.0 / d_axis if _path_key(path) in UNIT_KEYS else 1.0,
        params,
    )


def _validated_local_batches(local_batches, default_plan, mode, local_batch,
                             d_axis: int) -> np.ndarray:
    """The per-replica usable-sample table: the caller's override (bounds-
    checked) or the mode's default rule on ``default_plan``."""
    if local_batches is None:
        return default_local_batches(default_plan, mode, local_batch)
    lb = np.asarray(local_batches, dtype=np.int64)
    assert lb.shape == (d_axis,), (lb.shape, d_axis)
    assert ((lb >= 0) & (lb <= local_batch)).all(), (
        f"local_batches {lb} outside [0, {local_batch}]"
    )
    return lb


def default_local_batches(
    fplan, mode: Union[Mode, str], local_batch: int
) -> np.ndarray:
    """Per-replica usable local batch implied by the mode alone: UNIFORM
    keeps the full batch, NTP shrinks ∝ surviving TP (paper §3.1), DP_DROP
    zeroes every replica containing a failure. A `StagedPlan` is reduced by
    its slowest stage (`StagedPlan.effective`): 1F1B runs every microbatch
    through every stage, so the most-degraded stage gates the replica."""
    if isinstance(fplan, nu.StagedPlan):
        fplan = fplan.effective
    mode = Mode.coerce(mode)
    if mode is Mode.NTP:
        return fplan.local_batch_fraction(local_batch)
    if mode is Mode.DP_DROP:
        return np.array([
            local_batch if t == fplan.n1 else 0 for t in fplan.replica_tp
        ])
    return np.array([local_batch] * fplan.d)


def make_ntp_train_step(
    cfg: NTPModelConfig,
    fplan,
    mesh,
    *,
    mode: Union[Mode, str] = Mode.NTP,
    local_batch: int = 4,
    optimizer: Optional[Optimizer] = None,
    local_batches=None,
    microbatches: int = 1,
    overlap: bool = False,
):
    """Returns ``step`` with the same contract as train/steps.py:

        step(params, opt_state, batch) -> (params, opt_state, metrics)

    ``metrics`` carries at least ``loss`` and ``grad_norm``. The optimizer is
    pluggable (repro.optim.sgd / repro.optim.adamw) — the sync math, not the
    optimizer, is what NTP changes, so any elementwise update is legal on the
    packed buffers (every replica holds identical synced unit gradients and
    padded slots stay zero; DESIGN.md §2.3).

    ``local_batches``: optional per-replica usable-sample override (NTP-PW —
    a power-boosted degraded replica keeps MORE than its ∝-TP share, up to
    the full local batch; core/power.py + runtime/orchestrator.py decide).
    Defaults to the mode's own rule (`default_local_batches`).

    ``fplan`` may be a `StagedPlan` (nonuniform PP, DESIGN.md §2.6): each
    layer's gradients sync under its OWN stage's reshard plan (stage-local
    traffic). On a 2-axis ``(data, model)`` mesh the forward runs
    stage-sequentially over ``microbatches`` chunks (the 1F1B emulation;
    bubble cost is analytic — `core.perf_model`); on a staged
    ``(stage, data, model)`` mesh (`launch.mesh.make_staged_mesh`) the step
    is lowered onto per-stage submeshes with a `ppermute` activation
    hand-off, so bubble and cross-stage traffic are MEASURED
    (`core.pp_submesh`, DESIGN.md §2.8). A pp=1 `StagedPlan` (and
    ``microbatches=1``) takes the EXACT uniform-plan code path below, so the
    single-stage step is bit-identical to what this builder produced before
    stages existed.

    ``overlap=True`` switches to the overlapped, bucketed gradient sync
    (`core.overlap`, DESIGN.md §2.10): on the 2-axis mesh the step is
    rebuilt with a layer-chunked backward whose per-bucket sync issues
    while the previous chunk's backward runs (gradients match this
    builder's to f32 reassociation); on a staged submesh the pipeline step
    keeps its schedule and the sync collapses to one fused collective per
    (stage, plan-kind). ``overlap=False`` stays bit-identical to the
    pre-overlap step."""
    if isinstance(fplan, nu.StagedPlan) and fplan.pp == 1:
        fplan = fplan.stages[0]
    if isinstance(fplan, nu.StagedPlan) or microbatches > 1:
        from repro.core import pp_submesh

        if pp_submesh.is_staged_mesh(mesh):
            return pp_submesh.make_submesh_train_step(
                cfg, nu.as_staged(fplan), mesh, mode=mode,
                local_batch=local_batch, optimizer=optimizer,
                local_batches=local_batches, microbatches=microbatches,
                overlap=overlap,
            )
        if overlap:
            from repro.core import overlap as ov

            return ov.make_overlapped_train_step(
                cfg, nu.as_staged(fplan), mesh, mode=mode,
                local_batch=local_batch, optimizer=optimizer,
                local_batches=local_batches, microbatches=microbatches,
            )
        return _make_staged_train_step(
            cfg, nu.as_staged(fplan), mesh, mode=mode, local_batch=local_batch,
            optimizer=optimizer, local_batches=local_batches,
            microbatches=microbatches,
        )
    if overlap:
        from repro.core import overlap as ov

        return ov.make_overlapped_train_step(
            cfg, fplan, mesh, mode=mode, local_batch=local_batch,
            optimizer=optimizer, local_batches=local_batches,
            microbatches=microbatches,
        )
    mode = Mode.coerce(mode)
    optimizer = optimizer or sgd(1e-2)
    plans = _plans(cfg, fplan)
    d_axis = fplan.d
    lb = _validated_local_batches(local_batches, fplan, mode, local_batch,
                                  d_axis)
    lb_table = jnp.asarray(lb, jnp.int32)

    def global_loss(params, batch):
        """Scalar loss via shard_map; AD happens OUTSIDE the shard_map so
        jax seeds exactly one cotangent (grad-inside would seed one per rank
        and over-count every replicated path)."""
        specs = _tree_specs(params)

        moe_slots = (
            jnp.asarray(plans["mlp"].comp_slots, jnp.int32)
            if cfg.is_moe else None
        )

        def body(p_local, tokens_local):
            dd = jax.lax.axis_index("data")
            rr = jax.lax.axis_index("model")
            sample_mask = (
                jnp.arange(tokens_local.shape[0]) < lb_table[dd]
            ).astype(jnp.float32)
            p_sq = jax.tree_util.tree_map_with_path(_squeeze_unit, p_local)
            uids = moe_slots[dd, rr] if moe_slots is not None else None
            return _forward_local(cfg, p_sq, tokens_local, sample_mask, uids)

        return shard_map(
            body, mesh=mesh, in_specs=(specs, P("data", None)),
            out_specs=P(), check_vma=False,
        )(params, batch)

    # NTP gradient synchronization (paper §3.1/§4.1) on the global
    # unit-buffered grads: reshard -> psum('data') -> reshard, per weight —
    # the shared sequential body (core/overlap.make_sync_grads, a pp=1
    # StagedPlan degenerates to exactly the uniform-plan sync)
    from repro.core import overlap as ov

    sync_grads = ov.make_sync_grads(cfg, fplan, mesh, mode=mode)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(global_loss)(params, batch)
        grads = sync_grads(grads)
        with jax.named_scope("optimizer"):
            new_params, new_state, metrics = optimizer.update(
                grads, opt_state, params,
                norm_weights=_norm_weights(grads, d_axis),
            )
        metrics = dict(metrics, loss=loss)
        return new_params, new_state, metrics

    step.overlap = False
    step.collectives = sync_grads.collectives
    step.grads_fn = jax.jit(jax.value_and_grad(global_loss))
    step.sync_fn = jax.jit(sync_grads)
    return step


def _make_staged_train_step(
    cfg: NTPModelConfig,
    staged: nu.StagedPlan,
    mesh,
    *,
    mode: Union[Mode, str] = Mode.NTP,
    local_batch: int = 4,
    optimizer: Optional[Optimizer] = None,
    local_batches=None,
    microbatches: int = 1,
):
    """Stage-aware twin of `make_ntp_train_step` (DESIGN.md §2.6): the model
    is partitioned into ``staged.pp`` contiguous layer groups (boundaries
    from `configs.shapes.stage_boundaries`), each packed and synced under its
    own stage `FailurePlan`. The forward is microbatched stage-sequential —
    every microbatch walks the stages in order, activations handed along the
    layer loop (on this emulation every rank plays each stage in turn); the
    1F1B bubble ((pp-1)/m) is accounted analytically by `core.perf_model`,
    not by wall clock. Gradient sync is STAGE-LOCAL: layer grads reshard
    under their own stage's plan, so a failure in stage s moves no bytes for
    any other stage."""
    from repro.configs.shapes import layer_stages

    mode = Mode.coerce(mode)
    optimizer = optimizer or sgd(1e-2)
    stage_of = layer_stages(cfg.n_layers, staged.pp)
    stage_plans = [_plans(cfg, p) for p in staged.stages]
    eff = staged.effective
    d_axis = staged.d

    if not 1 <= microbatches <= local_batch:
        raise ValueError(
            f"microbatches={microbatches} outside [1, local_batch={local_batch}]"
        )
    if local_batch % microbatches:
        raise ValueError(
            f"local_batch={local_batch} not divisible by "
            f"microbatches={microbatches}"
        )
    lb = _validated_local_batches(local_batches, eff, mode, local_batch,
                                  d_axis)
    lb_table = jnp.asarray(lb, jnp.int32)

    def global_loss(params, batch):
        """Scalar loss via shard_map (AD outside, exactly as the uniform
        builder). Microbatch totals/counts accumulate BEFORE the data psum
        and the final normalization, so the loss value is the same full-batch
        mean the dense reference computes."""
        specs = _tree_specs(params)

        moe_slots = (
            [jnp.asarray(sp["mlp"].comp_slots, jnp.int32) for sp in stage_plans]
            if cfg.is_moe else None
        )

        def body(p_local, tokens_local):
            dd = jax.lax.axis_index("data")
            rr = jax.lax.axis_index("model")
            p_sq = jax.tree_util.tree_map_with_path(_squeeze_unit, p_local)
            uids = (
                [moe_slots[s][dd, rr] for s in stage_of]
                if moe_slots is not None else None
            )
            mb = tokens_local.shape[0] // microbatches
            total = jnp.float32(0.0)
            count = jnp.float32(0.0)
            for j in range(microbatches):
                toks = tokens_local[j * mb:(j + 1) * mb]
                mask = (
                    (j * mb + jnp.arange(mb)) < lb_table[dd]
                ).astype(jnp.float32)
                t, c = _forward_totals(cfg, p_sq, toks, mask, uids)
                total = total + t
                count = count + c
            total = jax.lax.psum(total, "data")
            count = jax.lax.psum(count, "data")
            return total / jnp.maximum(count, 1.0)

        return shard_map(
            body, mesh=mesh, in_specs=(specs, P("data", None)),
            out_specs=P(), check_vma=False,
        )(params, batch)

    # Stage-local NTP gradient sync (shared body — core/overlap): each
    # layer's unit grads reshard → psum('data') → reshard under its OWN
    # stage's plan; a healthy stage takes the plain psum fast path even
    # while another stage is degraded (no cross-stage traffic — the sync
    # collective never mixes stages).
    from repro.core import overlap as ov

    sync_grads = ov.make_sync_grads(cfg, staged, mesh, mode=mode)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(global_loss)(params, batch)
        grads = sync_grads(grads)
        with jax.named_scope("optimizer"):
            new_params, new_state, metrics = optimizer.update(
                grads, opt_state, params,
                norm_weights=_norm_weights(grads, d_axis),
            )
        metrics = dict(metrics, loss=loss)
        return new_params, new_state, metrics

    step.overlap = False
    step.collectives = sync_grads.collectives
    step.grads_fn = jax.jit(jax.value_and_grad(global_loss))
    step.sync_fn = jax.jit(sync_grads)
    return step
