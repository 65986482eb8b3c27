"""Pallas kernel validation: sweep shapes/dtypes/mask-kinds, allclose
against the pure-jnp oracles in kernels/ref.py, and flash attention against
the model's naive attention core (interpret mode on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 3e-5


def _naive_attention(q, k, v, kind, window, chunk, softcap):
    """models/attention.py's naive core in the kernel's (B, H, S, D) layout."""
    from repro.models import attention as am

    b, h, s, d = q.shape
    kvh = k.shape[1]
    pos = jnp.arange(s, dtype=jnp.int32)
    model_kind = {v: k for k, v in am.KERNEL_MASKS.items()}[kind]
    bias = am._mask_bias(model_kind, pos, pos, window, chunk)
    out = am._attend_naive(
        am._group(q.transpose(0, 2, 1, 3), kvh), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), bias[None, None, None], softcap)
    return out.reshape(b, h, s, d)


# (B, H, KVH, S, D): granite's 4 query heads a KV head at S 256, hd 64 with
# 128-blocks (2 x 2 tiles, the causal one above the diagonal skipped), and
# the forward-only kernel's earlier cases
FLASH_SHAPES = [
    (1, 8, 2, 256, 64),
    (1, 2, 1, 128, 64),
    (2, 4, 2, 256, 64),
    (1, 8, 8, 256, 128),   # MHA
    (2, 4, 1, 512, 32),    # MQA
]


KINDS = ["causal", "sliding", "chunked", "bidir"]
FLASH_CASES = (
    [(kind, shape, None, dtype) for kind in KINDS for shape in FLASH_SHAPES
     for dtype in (jnp.float32, jnp.bfloat16)]
    + [(kind, shape, 30.0, jnp.float32) for kind in KINDS
       for shape in FLASH_SHAPES[:2]]
)


@pytest.mark.parametrize("kind,shape,softcap,dtype", FLASH_CASES)
def test_flash_attention(kind, shape, softcap, dtype):
    """Forward and d/dq, d/dk, d/dv against the naive core at `highest`."""
    b, h, kvh, s, d = shape
    q = jnp.asarray(RNG.normal(size=(b, h, s, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, kvh, s, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, kvh, s, d)), dtype)
    ct = jnp.asarray(RNG.normal(size=(b, h, s, d)), jnp.float32)
    kw = dict(kind=kind, window=96, chunk=128, softcap=softcap)

    def kernel(q, k, v):
        return ops.flash_attention(q, k, v, block_q=128, block_k=128, **kw)

    def naive(q, k, v):
        return _naive_attention(q, k, v, **kw)

    def run(f):
        out, pullback = jax.vjp(f, q, k, v)
        return out, pullback(ct.astype(out.dtype))

    with jax.default_matmul_precision("highest"):
        (got, got_g), (want, want_g) = jax.jit(run, static_argnums=0)(
            kernel), jax.jit(run, static_argnums=0)(naive)
    assert got.dtype == dtype
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32)).max()
    assert err < _tol(dtype), (kind, dtype, err)
    for name, g, w in zip("qkv", got_g, want_g):
        assert g.dtype == dtype, name
        w = np.asarray(w, np.float32)
        gerr = np.abs(np.asarray(g, np.float32) - w).max() / (np.abs(w).max())
        assert gerr < _tol(dtype), (kind, dtype, name, gerr)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d,br", [(256, 128, 64), (512, 384, 256), (128, 512, 128)])
@pytest.mark.parametrize("plus_one", [False, True])
def test_rmsnorm(n, d, br, plus_one, dtype):
    x = jnp.asarray(RNG.normal(size=(n, d)), dtype)
    w = jnp.asarray(RNG.normal(size=(d,)) * 0.1, dtype)
    got = ops.rmsnorm(x, w, plus_one=plus_one, block_rows=br)
    want = ref.rmsnorm_ref(x, w, plus_one=plus_one)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32)).max()
    assert err < _tol(dtype)


@pytest.mark.parametrize("bh,s,hp,ds,chunk", [
    (2, 64, 16, 32, 16), (3, 128, 16, 32, 32), (1, 256, 64, 128, 64),
    (2, 512, 64, 128, 128),   # TPU-legal tiles, 4 chunks per row
])
def test_ssd_scan(bh, s, hp, ds, chunk):
    x = jnp.asarray(RNG.normal(size=(bh, s, hp)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(bh, s)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(bh,)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(bh, s, ds)) * 0.3, jnp.float32)
    C = jnp.asarray(RNG.normal(size=(bh, s, ds)) * 0.3, jnp.float32)
    got = ops.ssd_scan(x, dt, A, B, C, chunk=chunk)
    want = ref.ssd_scan_ref(x, dt, A, B, C)
    err = np.abs(np.asarray(got) - np.asarray(want)).max()
    assert err < 5e-4, err


def test_ssd_kernel_matches_model_path():
    """Kernel == models/ssm.py chunked implementation (two formulations)."""
    from repro.models.ssm import _ssd_chunked

    b, s, nh, hp, ds = 2, 64, 3, 16, 32
    x = jnp.asarray(RNG.normal(size=(b, s, nh, hp)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(b, s, nh)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(nh,)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(b, s, ds)) * 0.3, jnp.float32)
    C = jnp.asarray(RNG.normal(size=(b, s, ds)) * 0.3, jnp.float32)
    h0 = jnp.zeros((b, nh, hp, ds), jnp.float32)
    want, _ = _ssd_chunked(x, dt, A, B, C, h0, 16)

    xk = x.transpose(0, 2, 1, 3).reshape(b * nh, s, hp)
    dtk = dt.transpose(0, 2, 1).reshape(b * nh, s)
    Ak = jnp.tile(A, b)
    Bk = jnp.repeat(B[:, None], nh, 1).reshape(b * nh, s, ds)
    Ck = jnp.repeat(C[:, None], nh, 1).reshape(b * nh, s, ds)
    got = ops.ssd_scan(xk, dtk, Ak, Bk, Ck, chunk=16)
    got = got.reshape(b, nh, s, hp).transpose(0, 2, 1, 3)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 5e-4


@pytest.mark.parametrize("u,elems,n,smax", [
    (10, 8, 4, 5), (33, 128, 8, 9),
    (3, 128 * 4096, 2, 2),    # unit rows copied in 2 row blocks each
])
def test_reshard_pack(u, elems, n, smax):
    src = jnp.asarray(
        np.vstack([RNG.normal(size=(u, elems)), np.zeros((1, elems))]),
        jnp.float32,
    )
    idx = jnp.asarray(RNG.integers(0, u + 1, size=(n, smax)), jnp.int32)
    got = ops.reshard_pack(src, idx)
    want = ref.reshard_pack_ref(src, idx)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# shape validation (ISSUE 7 satellite): must survive `python -O` — ValueError,
# not assert — and name BOTH the offending dimension and the block-size
# argument, so a bad launcher flag is diagnosable from the message alone.

def test_rmsnorm_rejects_indivisible_rows():
    x = jnp.zeros((96, 8), jnp.float32)
    w = jnp.ones((8,), jnp.float32)
    with pytest.raises(ValueError,
                       match=r"rmsnorm: row count n=96 .* block_rows=64"):
        ops.rmsnorm(x, w, block_rows=64, interpret=True)


def test_flash_attention_rejects_indivisible_blocks():
    q = jnp.zeros((1, 2, 48, 16), jnp.float32)
    k = jnp.zeros((1, 1, 48, 16), jnp.float32)
    v = jnp.zeros((1, 1, 48, 16), jnp.float32)
    with pytest.raises(
            ValueError,
            match=r"sequence length s=48 .* query-block size block_q=32"):
        ops.flash_attention(q, k, v, block_q=32, block_k=48, interpret=True)
    with pytest.raises(
            ValueError,
            match=r"sequence length s=48 .* key-block size block_k=32"):
        ops.flash_attention(q, k, v, block_q=48, block_k=32, interpret=True)


def test_ssd_scan_rejects_indivisible_chunk():
    x = jnp.zeros((2, 48, 4), jnp.float32)
    dt = jnp.zeros((2, 48), jnp.float32)
    A = jnp.zeros((2,), jnp.float32)
    B = jnp.zeros((2, 48, 8), jnp.float32)
    with pytest.raises(ValueError,
                       match=r"sequence length s=48 .* chunk length chunk=32"):
        ops.ssd_scan(x, dt, A, B, B, chunk=32, interpret=True)
