"""Step functions (train / prefill / decode) with full sharding metadata.

``make_setup`` assembles, for one (arch × input-shape × mesh):
  * the model (with activation-sharding constraints bound to the mesh),
  * parameter / optimizer-state / cache shardings,
  * the jittable step function + its in/out shardings,
so launch/train.py, launch/dryrun.py, benchmarks and tests all share one
code path. mesh=None gives the single-device variant used by unit tests.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.configs.shapes import ShapeSpec
from repro.data.pipeline import input_specs
from repro.models import ShardCtx, build_model
from repro.models.common import NO_SHARD
from repro.optim import AdamWConfig, adamw_init, adamw_update, warmup_cosine
from repro.sharding import param_shardings
from repro.sharding.specs import zero1_shardings


# ---------------------------------------------------------------------------
# loss

@jax.named_scope("loss_head")
def cross_entropy(logits, targets, real_vocab: int):
    """Mean next-token CE over (B,S). Handles Megatron vocab padding by
    masking padded logits; fp32 reductions."""
    logits = logits.astype(jnp.float32)
    vp = logits.shape[-1]
    if vp != real_vocab:
        pad = jnp.arange(vp) >= real_vocab
        logits = jnp.where(pad[None, None, :], -1e30, logits)
    m = jax.lax.stop_gradient(logits.max(-1, keepdims=True))
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


# ---------------------------------------------------------------------------
# setup bundle

@dataclass
class Setup:
    cfg: ArchConfig
    shape: ShapeSpec
    mesh: Optional[Mesh]
    model: Any
    opt_cfg: Optional[AdamWConfig]
    # shardings (None when mesh is None)
    param_sharding: Any = None
    opt_sharding: Any = None
    cache_sharding: Any = None
    batch_specs: Dict[str, jax.ShapeDtypeStruct] = field(default_factory=dict)
    # step callables (un-jitted)
    step_fn: Callable = None
    # jit kwargs
    in_shardings: Any = None
    out_shardings: Any = None
    donate_argnums: Tuple[int, ...] = ()

    def jit_step(self):
        kw = {}
        if self.mesh is not None:
            kw = dict(in_shardings=self.in_shardings, out_shardings=self.out_shardings)
        return jax.jit(self.step_fn, donate_argnums=self.donate_argnums, **kw)

    def abstract_args(self, key=jax.random.PRNGKey(0)):
        """ShapeDtypeStruct args for .lower() — no allocation."""
        pshape = jax.eval_shape(self.model.init, key)
        args = [_attach(pshape, self.param_sharding)]
        if self.shape.kind == "train":
            oshape = jax.eval_shape(lambda p: adamw_init(p, self.opt_cfg), pshape)
            args.append(_attach(oshape, self.opt_sharding))
            args.append(dict(self.batch_specs))
        elif self.shape.kind == "prefill":
            args.append(dict(self.batch_specs))
        else:  # decode
            cshape = jax.eval_shape(
                lambda: self.model.init_cache(
                    self.shape.global_batch, self.shape.seq_len, jnp.bfloat16
                )
            )
            args.append(_attach(cshape, self.cache_sharding))
            args.append(dict(self.batch_specs))
        return tuple(args)


def _attach(shape_tree, sharding_tree):
    if sharding_tree is None:
        return shape_tree
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shape_tree,
        sharding_tree,
    )


# ---------------------------------------------------------------------------

def make_setup(
    cfg: ArchConfig,
    shape: ShapeSpec,
    mesh: Optional[Mesh] = None,
    *,
    dp_axes: Tuple[str, ...] = ("data",),
    param_dtype=jnp.bfloat16,
    opt_cfg: Optional[AdamWConfig] = None,
    remat: bool = True,
    scan_unroll: bool = False,
    microbatches: int = 1,
    lr_schedule: Callable = functools.partial(warmup_cosine, warmup=100, total=10_000),
) -> Setup:
    """``microbatches`` > 1 splits each train step's batch into that many
    chunks and accumulates gradients across them (the PP-style 1F1B schedule
    at the arch-stack level — one optimizer update per step, peak activation
    memory ∝ 1/m; `core.perf_model.iteration_time` charges the matching
    (pp-1)/m bubble). ``microbatches=1`` is the exact unchanged step."""
    if shape.kind == "train":
        if not 1 <= microbatches <= shape.global_batch:
            raise ValueError(
                f"microbatches={microbatches} outside "
                f"[1, global_batch={shape.global_batch}]"
            )
        if shape.global_batch % microbatches:
            raise ValueError(
                f"global_batch={shape.global_batch} not divisible by "
                f"microbatches={microbatches}"
            )
        if microbatches > 1 and cfg.moe is not None:
            # the MoE load-balance aux loss is nonlinear in per-batch
            # routing statistics: mean-of-chunk aux != full-batch aux, so
            # grad accumulation would NOT equal the microbatches=1 step —
            # refuse rather than silently change training with m
            raise ValueError(
                f"microbatches={microbatches} with a MoE arch "
                f"({cfg.arch_id}): the load-balance aux loss is not "
                "additive over microbatch chunks, so accumulated grads "
                "would differ from the full-batch step"
            )
    elif microbatches != 1:
        raise ValueError(f"microbatches only applies to train shapes, "
                         f"got kind={shape.kind!r}")
    # C2 gate (measured, EXPERIMENTS.md §Perf): SP wins on train steps for
    # non-rglru / non-post-norm archs; it loses slightly on prefill (no
    # backward to amortize the extra seq<->head transitions) and on rglru
    # (sequence recurrence) / post-norm archs (extra transitions).
    sp = (
        shape.kind == "train"
        and "rglru" not in cfg.layer_pattern
        and not cfg.post_norms
    )
    ctx = ShardCtx(mesh=mesh, dp=dp_axes, sp=sp) if mesh is not None else NO_SHARD
    model = build_model(cfg, ctx, param_dtype=param_dtype, remat=remat)
    model.scan_unroll = scan_unroll
    opt_cfg = opt_cfg or AdamWConfig()
    bspecs = input_specs(cfg, shape, mesh, dp_axes)

    su = Setup(cfg=cfg, shape=shape, mesh=mesh, model=model, opt_cfg=opt_cfg,
               batch_specs=bspecs)

    if mesh is not None:
        pshape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        su.param_sharding = param_shardings(mesh, model.param_specs(), pshape)
        if shape.kind == "train":
            oshape = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), pshape)
            ospec = {
                "m": model.param_specs(),
                "v": model.param_specs(),
                "step": P(),
            }
            if "master" in oshape:
                ospec["master"] = model.param_specs()
            su.opt_sharding = {
                k: (
                    zero1_shardings(mesh, ospec[k], oshape[k], dp_axes)
                    if k != "step"
                    else NamedSharding(mesh, P())
                )
                for k in oshape
            }
        if shape.kind == "decode":
            cshape = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len, jnp.bfloat16)
            )
            cspec = model.cache_specs(cshape)
            su.cache_sharding = jax.tree.map(
                lambda sp: NamedSharding(mesh, sp), cspec,
                is_leaf=lambda x: isinstance(x, P),
            )

    # ---- step functions ---------------------------------------------------
    if shape.kind == "train":

        def loss_fn(params, batch):
            logits, aux = model.forward(
                params, batch["tokens"], enc_input=batch.get("enc_input")
            )
            loss = cross_entropy(logits, batch["targets"], cfg.vocab_size)
            return loss + aux["moe_aux_loss"], loss

        def train_step(params, opt_state, batch):
            (total, ce), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            with jax.named_scope("optimizer"):
                lr_scale = lr_schedule(opt_state["step"])
                params, opt_state, metrics = adamw_update(
                    grads, opt_state, params, opt_cfg, lr_scale
                )
            metrics.update(loss=ce, total_loss=total)
            return params, opt_state, metrics

        def microbatched_train_step(params, opt_state, batch):
            # stage-sequential 1F1B emulation: each microbatch runs the full
            # forward/backward; grads (mean-per-microbatch) average to the
            # full-batch gradient since every chunk has equal size
            m = microbatches
            mb = shape.global_batch // m
            grads = None
            total = ce = jnp.float32(0.0)
            for j in range(m):
                sl = {k: (v[j * mb:(j + 1) * mb]
                          if hasattr(v, "ndim") and v.ndim >= 1
                          and v.shape[0] == shape.global_batch else v)
                      for k, v in batch.items()}
                (t, c), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, sl
                )
                total, ce = total + t, ce + c
                grads = g if grads is None else jax.tree.map(
                    jnp.add, grads, g
                )
            grads = jax.tree.map(lambda x: x / m, grads)
            with jax.named_scope("optimizer"):
                lr_scale = lr_schedule(opt_state["step"])
                params, opt_state, metrics = adamw_update(
                    grads, opt_state, params, opt_cfg, lr_scale
                )
            metrics.update(loss=ce / m, total_loss=total / m,
                           microbatches=jnp.int32(m))
            return params, opt_state, metrics

        su.step_fn = train_step if microbatches == 1 else microbatched_train_step
        su.donate_argnums = (0, 1)
        if mesh is not None:
            su.in_shardings = (
                su.param_sharding,
                su.opt_sharding,
                {k: v.sharding for k, v in bspecs.items()},
            )
            su.out_shardings = (
                su.param_sharding,
                su.opt_sharding,
                None,
            )

    elif shape.kind == "prefill":

        def prefill_step(params, batch):
            cache = model.init_cache(shape.global_batch, shape.seq_len, jnp.bfloat16)
            logits, cache = model.prefill(
                params, batch["tokens"], cache, enc_input=batch.get("enc_input")
            )
            return logits[:, -1], cache

        su.step_fn = prefill_step
        if mesh is not None:
            cshape = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len, jnp.bfloat16)
            )
            cspec = model.cache_specs(cshape)
            cache_sh = jax.tree.map(
                lambda sp: NamedSharding(mesh, sp), cspec,
                is_leaf=lambda x: isinstance(x, P),
            )
            su.cache_sharding = cache_sh
            su.in_shardings = (
                su.param_sharding,
                {k: v.sharding for k, v in bspecs.items()},
            )
            su.out_shardings = (None, cache_sh)

    else:  # decode

        def serve_step(params, cache, batch):
            logits, cache = model.decode_step(
                params, cache, batch["tokens"], batch["pos"],
                enc_out=batch.get("enc_out"),
            )
            return logits[:, 0], cache

        su.step_fn = serve_step
        su.donate_argnums = (1,)
        if mesh is not None:
            su.in_shardings = (
                su.param_sharding,
                su.cache_sharding,
                {k: (v.sharding if v.sharding is not None else None)
                 for k, v in bspecs.items()},
            )
            su.out_shardings = (None, su.cache_sharding)

    return su
