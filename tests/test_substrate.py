"""Substrate unit tests: optimizer, data pipeline, checkpointing, loss,
MoE dispatch, HLO analyzer, attention flash path."""
import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis", reason="dev dependency: pip install -e .[dev]")
from hypothesis import given, settings, strategies as st

from repro.optim import AdamWConfig, adamw_init, adamw_update, warmup_cosine
from repro.train.steps import cross_entropy


# ---------------------------------------------------------------------------
# optimizer

def test_adamw_reduces_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw_init(params, cfg)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw_update(grads, state, params, cfg)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_adamw_master_weights_bf16():
    cfg = AdamWConfig(lr=1e-3)
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    state = adamw_init(params, cfg)
    assert "master" in state and state["master"]["w"].dtype == jnp.float32
    grads = {"w": jnp.full((4,), 0.01, jnp.bfloat16)}
    params, state, _ = adamw_update(grads, state, params, cfg)
    assert params["w"].dtype == jnp.bfloat16


def test_grad_clip():
    cfg = AdamWConfig(lr=0.0, grad_clip=1.0)
    params = {"w": jnp.zeros((3,))}
    state = adamw_init(params, cfg)
    _, _, m = adamw_update({"w": jnp.full((3,), 100.0)}, state, params, cfg)
    assert float(m["grad_norm"]) > 100


def test_warmup_cosine_shape():
    assert float(warmup_cosine(jnp.int32(0), warmup=10, total=100)) == 0.0
    assert abs(float(warmup_cosine(jnp.int32(10), warmup=10, total=100)) - 1.0) < 1e-6
    assert float(warmup_cosine(jnp.int32(100), warmup=10, total=100)) <= 0.11


# ---------------------------------------------------------------------------
# loss

def test_cross_entropy_matches_naive():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 5, 16)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, 12, size=(2, 5)))
    got = cross_entropy(logits, targets, 16)
    want = -jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1), targets[..., None], -1
    ).mean()
    assert abs(float(got) - float(want)) < 1e-5


def test_cross_entropy_vocab_padding_invariant():
    """Adding padded vocab columns must not change the loss."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(2, 5, 12)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, 12, size=(2, 5)))
    padded = jnp.concatenate(
        [logits, jnp.full((2, 5, 4), 7.7, jnp.float32)], axis=-1
    )
    a = cross_entropy(logits, targets, 12)
    b = cross_entropy(padded, targets, 12)
    assert abs(float(a) - float(b)) < 1e-5


# ---------------------------------------------------------------------------
# data pipeline

def test_pipeline_deterministic_and_learnable():
    from repro.data.pipeline import DataConfig, SyntheticLMPipeline

    cfg = DataConfig(vocab_size=101, seq_len=32, global_batch=4, seed=7)
    p1, p2 = SyntheticLMPipeline(cfg), SyntheticLMPipeline(cfg)
    b1, b2 = p1.batch(3), p2.batch(3)
    assert np.array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    # targets are the shifted stream
    arr1 = p1._batch_np(5)
    assert np.array_equal(arr1[:, 1:-1], p1._batch_np(5)[:, 1:-1])
    # mostly-deterministic transitions (noise=0.05)
    toks, tgt = arr1[:, :-1], arr1[:, 1:]
    pred = (p1.a * toks + p1.b) % cfg.vocab_size
    assert (pred == tgt).mean() > 0.85


def test_input_specs_cover_all_shapes(arch_ids):
    from repro.configs import SHAPES, get_arch
    from repro.data.pipeline import input_specs

    for aid in arch_ids:
        for sh in SHAPES.values():
            specs = input_specs(get_arch(aid), sh)
            assert "tokens" in specs
            if sh.kind == "decode":
                assert specs["tokens"].shape[1] == 1 and "pos" in specs
            if get_arch(aid).encoder is not None and sh.kind != "decode":
                assert "enc_input" in specs


# ---------------------------------------------------------------------------
# checkpoint

def test_checkpoint_roundtrip():
    from repro.checkpoint import load_checkpoint, save_checkpoint

    tree = {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "nested": {"b": jnp.ones((4,), jnp.bfloat16)},
        "list": [jnp.zeros((2,)), jnp.full((2,), 3.0)],
    }
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ck.npz")
        save_checkpoint(path, tree, step=17)
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        got, step = load_checkpoint(path, like)
        assert step == 17
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
            assert np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


# ---------------------------------------------------------------------------
# MoE dispatch vs dense oracle

def test_moe_sort_dispatch_matches_dense():
    import dataclasses

    from repro.configs import get_arch, reduced
    from repro.models.common import NO_SHARD
    from repro.models.mlp import moe_apply, moe_apply_dense_ref, moe_init

    cfg = reduced(get_arch("arctic-480b"))
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0)
    )
    p = moe_init(cfg, jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model)) * 0.5
    got, aux = moe_apply(cfg, p, x, NO_SHARD)
    want = moe_apply_dense_ref(cfg, p, x, NO_SHARD)
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert float(aux["moe_aux_loss"]) >= 0


def test_moe_capacity_drops_bounded():
    """With cf=1.0 some tokens may drop but output stays finite/close."""
    import dataclasses

    from repro.configs import get_arch, reduced
    from repro.models.common import NO_SHARD
    from repro.models.mlp import moe_apply, moe_init

    cfg = reduced(get_arch("llama4-scout-17b-a16e"))
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=1.0)
    )
    p = moe_init(cfg, jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    got, _ = moe_apply(cfg, p, x, NO_SHARD)
    assert bool(jnp.isfinite(got).all())


# ---------------------------------------------------------------------------
# flash (jnp double-scan) path == naive path

def test_attention_flash_path_matches_naive():
    from repro.configs import get_arch, reduced
    from repro.models import attention as am
    from repro.models.common import NO_SHARD

    cfg = reduced(get_arch("gemma2-9b"))  # softcap + sliding window coverage
    p = am.attn_init(cfg, jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2048, cfg.d_model)) * 0.3

    for kind in ("attn", "attn_sw"):
        naive, _ = am.attn_apply(cfg, p, x, kind=kind, ctx=NO_SHARD)
        old = am.FLASH_SEQ_THRESHOLD
        am.FLASH_SEQ_THRESHOLD = 1024  # force the blockwise path
        try:
            flash, _ = am.attn_apply(cfg, p, x, kind=kind, ctx=NO_SHARD)
        finally:
            am.FLASH_SEQ_THRESHOLD = old
        err = float(jnp.abs(naive - flash).max())
        assert err < 2e-4, (kind, err)


# ---------------------------------------------------------------------------
# which core attn_apply takes: the Pallas flash kernel only on the TPU, with
# no head-sharding mesh, for cache-free self-attention on whole blocks

@pytest.mark.parametrize("change,reason", [
    ({}, None),
    ({"tp": 1}, None),
    ({"kind": "attn_sw"}, None),
    ({"kind": "attn_bidir", "s": 8192}, None),
    ({"backend": "cpu"}, "backend"),
    ({"tp": 2}, "tp_sharded"),
    ({"cross": True}, "cross"),
    ({"cached": True}, "cache"),
    ({"kind": "attn_local"}, "kind"),
    ({"s": 256}, "seq_len"),
    ({"s": 512}, None),
    ({"s": 1536}, None),
    ({"s": 640}, "seq_len"),
])
def test_attention_dispatch_predicate(change, reason):
    from jax.sharding import AbstractMesh

    from repro.models import attention as am
    from repro.models.common import ShardCtx

    kw = dict(kind="attn", s=4096, hd=64, cross=False, cached=False,
              tp=None, backend="tpu")
    kw.update(change)
    tp = kw.pop("tp")
    mesh = None if tp is None else AbstractMesh((1, tp), ("data", "model"))
    kind, s, hd = kw.pop("kind"), kw.pop("s"), kw.pop("hd")
    assert am.xla_reason(kind, s, hd, ctx=ShardCtx(mesh=mesh), **kw) == reason


def _attention_paths(cfg, s, ctx):
    from repro import telemetry
    from repro.models import attention as am

    p = am.attn_init(cfg, jax.random.PRNGKey(0), jnp.float32)
    x = jax.ShapeDtypeStruct((2, s, cfg.d_model), jnp.float32)
    sink = telemetry.MemorySink()
    with telemetry.recording(telemetry.Recorder(sinks=[sink])):
        jax.eval_shape(
            lambda x: am.attn_apply(cfg, p, x, kind="attn", ctx=ctx)[0], x)
    return {(e["labels"]["path"], e["labels"].get("reason"))
            for e in sink.events(name="attention.path")}


def test_attention_records_xla_path_on_cpu():
    from repro.configs import get_arch, reduced
    from repro.models.common import NO_SHARD

    cfg = reduced(get_arch("granite-3-2b"))
    assert _attention_paths(cfg, 1024, NO_SHARD) == {("xla", "backend")}


@pytest.mark.parametrize("tp,path", [(2, ("xla", "tp_sharded")),
                                     (1, ("kernel", None))])
def test_attention_records_path_under_a_tp_mesh(monkeypatch, tp, path):
    """As on the TPU (the backend steered here): a head-sharded call stays
    on XLA, a mesh whose TP axis has size 1 takes the kernel."""
    from jax.sharding import AbstractMesh

    from repro.configs import get_arch, reduced
    from repro.models.common import ShardCtx

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = reduced(get_arch("granite-3-2b"))
    ctx = ShardCtx(mesh=AbstractMesh((1, tp), ("data", "model")))
    assert _attention_paths(cfg, 1024, ctx) == {path}


# ---------------------------------------------------------------------------
# HLO analyzer

def test_hlo_analyzer_exact_matmul():
    from repro.launch.hlo_analysis import analyze_hlo

    a = jnp.zeros((256, 512), jnp.float32)
    b = jnp.zeros((512, 128), jnp.float32)
    txt = jax.jit(lambda a, b: a @ b).lower(a, b).compile().as_text()
    r = analyze_hlo(txt)
    assert r["flops"] == 2 * 256 * 512 * 128


def test_hlo_analyzer_scan_trip_count():
    from repro.launch.hlo_analysis import analyze_hlo

    b0 = jnp.eye(128)

    def g(a):
        return jax.lax.scan(lambda c, _: (c @ b0, None), a, None, length=7)[0]

    txt = jax.jit(g).lower(jnp.zeros((128, 128))).compile().as_text()
    r = analyze_hlo(txt)
    want = 2 * 128**3 * 7
    assert want <= r["flops"] < want * 1.02
    assert r["unknown_trip_count_loops"] == 0
