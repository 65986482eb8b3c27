"""Deterministic synthetic data pipeline + dry-run input specs.

``input_specs`` is the single source of truth for every model input's shape,
dtype and sharding (ShapeDtypeStruct stand-ins — no allocation), used by both
the real pipeline (for array layout) and the multi-pod dry-run.

The synthetic corpus is a seeded affine Markov stream: learnable structure
(so examples/quickstart loss actually drops) with zero I/O dependencies.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import telemetry
from repro.configs.base import ArchConfig
from repro.configs.shapes import ShapeSpec
from repro.models.common import sanitize_spec


# ---------------------------------------------------------------------------
# dry-run input specs

def input_specs(
    cfg: ArchConfig,
    shape: ShapeSpec,
    mesh: Optional[Mesh] = None,
    dp_axes: Tuple[str, ...] = ("data",),
) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStructs (weak-type-correct, shardable, no allocation) for
    every *data* input of the step function for (cfg, shape).

    train   -> tokens/targets (B, S)
    prefill -> tokens (B, S)
    decode  -> tokens (B, 1) + pos ()   (the cache is built by the step; see
               repro.train.steps.cache_specs)
    Audio archs additionally get enc_input (B, enc_seq, d) frame embeddings
    (the frontend stub per the brief).
    """
    b, s = shape.global_batch, shape.seq_len

    def sds(shp, dtype, spec):
        if mesh is None:
            return jax.ShapeDtypeStruct(shp, dtype)
        sp = sanitize_spec(dict(mesh.shape), shp, spec)
        return jax.ShapeDtypeStruct(shp, dtype, sharding=NamedSharding(mesh, sp))

    batch_spec = P(dp_axes)
    out: Dict[str, jax.ShapeDtypeStruct] = {}
    if shape.kind == "train":
        out["tokens"] = sds((b, s), jnp.int32, batch_spec)
        out["targets"] = sds((b, s), jnp.int32, batch_spec)
    elif shape.kind == "prefill":
        out["tokens"] = sds((b, s), jnp.int32, batch_spec)
    elif shape.kind == "decode":
        out["tokens"] = sds((b, 1), jnp.int32, batch_spec)
        out["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
    else:
        raise ValueError(shape.kind)

    if cfg.encoder is not None and shape.kind in ("train", "prefill"):
        out["enc_input"] = sds(
            (b, cfg.encoder.enc_seq, cfg.d_model), jnp.bfloat16, batch_spec
        )
    if cfg.encoder is not None and shape.kind == "decode":
        out["enc_out"] = sds(
            (b, cfg.encoder.enc_seq, cfg.d_model), jnp.bfloat16, batch_spec
        )
    return out


# ---------------------------------------------------------------------------
# synthetic corpus

@dataclass(frozen=True)
class DataConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    noise: float = 0.05  # fraction of uniformly-random tokens


class SyntheticLMPipeline:
    """Affine Markov token stream: t_{i+1} = (a·t_i + b) mod V, with a small
    uniform-noise fraction. Deterministic given (seed, step)."""

    def __init__(self, cfg: DataConfig, mesh: Optional[Mesh] = None,
                 dp_axes: Tuple[str, ...] = ("data",)):
        self.cfg = cfg
        self.mesh = mesh
        self.dp_axes = dp_axes
        rng = np.random.default_rng(cfg.seed)
        v = cfg.vocab_size
        # affine params coprime-ish with V for long cycles
        self.a = int(rng.integers(2, max(3, v - 1))) | 1
        self.b = int(rng.integers(1, v))

    def _batch_np(self, step: int) -> np.ndarray:
        c = self.cfg
        rng = np.random.default_rng((c.seed, step))
        t0 = rng.integers(0, c.vocab_size, size=(c.global_batch, 1))
        toks = [t0]
        for _ in range(c.seq_len):
            nxt = (self.a * toks[-1] + self.b) % c.vocab_size
            noise_mask = rng.random((c.global_batch, 1)) < c.noise
            rand = rng.integers(0, c.vocab_size, size=(c.global_batch, 1))
            toks.append(np.where(noise_mask, rand, nxt))
        return np.concatenate(toks, axis=1).astype(np.int32)  # (B, S+1)

    def batch(self, step: int) -> Dict[str, jnp.ndarray]:
        with telemetry.get().span("data.batch"):
            arr = self._batch_np(step)
            tokens, targets = arr[:, :-1], arr[:, 1:]
            if self.mesh is not None:
                sh = NamedSharding(self.mesh, P(self.dp_axes))
                tokens = jax.device_put(tokens, sh)
                targets = jax.device_put(targets, sh)
            else:
                tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
        return {"tokens": tokens, "targets": targets}

    def __iter__(self) -> Iterator[Dict[str, jnp.ndarray]]:
        step = 0
        while True:
            yield self.batch(step)
            step += 1
