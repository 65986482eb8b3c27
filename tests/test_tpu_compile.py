"""Every Pallas kernel compiles for a TPU v5e at real widths.

Nothing runs: the TPU compiler, which is installed alongside jax, compiles
each kernel for a described (not attached) v5e:2x2 chip and refuses what
the chip would refuse — tiles that break the (8, 128) rule, blocks that
overflow scoped VMEM. Interpret-mode parity lives in test_kernels.py,
test_overlap.py and test_reshard_pack_modes.py. The topology is described
inside a fixture (never at import), so a test worker that cannot load the
TPU library skips these tests instead of breaking collection.

Widths: granite-3-2b (d_model 2048, 32H/8KV, head_dim 64, d_ff 8192) at
seq 2048 and, for attention and its train step, 4096; qwen2-7b attention
(28H/4KV, head_dim 128) at seq 4096; mamba2-780m's SSD (48 heads x 64,
d_state 128, chunk 256).
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import bucket, flash_attention, reshard_pack, rmsnorm
from repro.kernels import ssd_scan


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU library, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.mark.parametrize("h,kvh,s,d", [(32, 8, 4096, 64), (28, 4, 4096, 128)])
def test_flash_attention_compiles(one_chip, no_cache, h, kvh, s, d):
    """Forward, and forward with the fused backward, f32 operands as the
    model passes them."""
    def fwd(q, k, v):
        return flash_attention.flash_attention(q, k, v, interpret=False)

    shapes = (((2, h, s, d), F32), ((2, kvh, s, d), F32),
              ((2, kvh, s, d), F32))
    _compile(one_chip, fwd, *shapes)
    _compile(one_chip, lambda q, k, v: jax.grad(
        lambda *a: fwd(*a).sum(), argnums=(0, 1, 2))(q, k, v), *shapes)


def test_granite_train_step_runs_attention_in_the_kernel(
        one_chip, no_cache, monkeypatch):
    """granite-3-2b's train step at 1 layer, 2 x 4096 tokens: on the TPU
    (steered here; the compile runs on the CPU) attention's forward, its
    recomputed forward and its fused backward are three kernel calls in
    the `attention` scope, and no f32 S x S array is left in the step's
    scratch (one is 2 x 32 x 4096^2 x 4 B = 4.29 GB; the XLA path held
    9.52 GB of scratch here)."""
    import dataclasses
    import re

    from repro.configs import get_arch
    from repro.configs.shapes import ShapeSpec
    from repro.train.steps import make_setup

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_arch("granite-3-2b"), n_layers=1)
    b, s = 2, 4096
    su = make_setup(cfg, ShapeSpec("t", s, b, "train"), None,
                    param_dtype=F32)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        su.abstract_args())
    compiled = su.jit_step().lower(*args).compile()
    text = compiled.as_text()
    names = [re.search(r'op_name="([^"]*)"', text[m.end():]).group(1)
             for m in re.finditer(r'custom_call_target="tpu_custom_call"',
                                  text)]
    assert len(names) == 3, names
    assert all("/attention/" in n for n in names), names
    # the backward kernels, and the recomputed forward, read as backward
    assert sum("transpose(" not in n for n in names) == 1, names
    scores = b * cfg.n_heads * s * s * 4
    assert compiled.memory_analysis().temp_size_in_bytes < scores


@pytest.mark.parametrize("d", [2048, 3584])
def test_rmsnorm_compiles(one_chip, no_cache, d):
    _compile(one_chip, lambda x, w: rmsnorm.rmsnorm(x, w, interpret=False),
             ((4 * 2048, d), F32), ((d,), F32))


def test_ssd_scan_compiles(one_chip, no_cache):
    bh, s, hp, ds = 2 * 48, 2048, 64, 128
    _compile(one_chip,
             lambda x, dt, a, b, c: ssd_scan.ssd_scan(x, dt, a, b, c,
                                                      interpret=False),
             ((bh, s, hp), F32), ((bh, s), F32), ((bh,), F32),
             ((bh, s, ds), F32), ((bh, s, ds), F32))


@pytest.mark.parametrize("units,elems", [
    (17, 2048 * 128),      # granite MLP units (d_model x 128 rows), TP4 buf
    (9, 2048 * 256),       # granite attention kv-group units (wq)
    (9, 200),              # a width that is not a 128-multiple
])
def test_reshard_pack_compiles(one_chip, no_cache, units, elems):
    compiled = _compile(
        one_chip, lambda s, i: reshard_pack.reshard_pack(s, i,
                                                         interpret=False),
        ((units, elems), F32), ((4, 6), I32))
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("rows,widths", [
    (148, (2048 * 128, 2048 * 128)),    # the bucket that overflowed VMEM
    (16, (2048 * 256, 2048 * 64, 2048 * 64, 256 * 2048)),  # a layer's attn
    (1, (2048, 2048)),                   # replicated ln leaves
])
def test_bucket_pack_unpack_compile(one_chip, no_cache, rows, widths):
    _compile(one_chip,
             lambda *ls: bucket.bucket_pack(ls, interpret=False),
             *[((rows, w), F32) for w in widths])
    _compile(one_chip,
             lambda f: bucket.bucket_unpack(f, widths, interpret=False),
             ((rows, sum(widths)), F32))
