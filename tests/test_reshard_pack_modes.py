"""Pallas `reshard_pack` kernel-vs-jnp parity across BOTH execution modes:
kernels are compiled wherever a non-CPU device exists and interpreted on
CPU (`kernels.mode.pallas_interpret`: explicit ``interpret=True`` >
backend default, read per call). Interpret mode must match the plain jnp
gather bit-for-bit on every backend; compiled mode is asserted identical
too wherever the backend can lower Pallas (TPU/GPU), and skips cleanly on
CPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.reshard import engine as rse
from repro.reshard import planner


def _case(seed=0, k=6, n1=4, tp_to=2):
    rng = np.random.default_rng(seed)
    tables = planner.tables(
        planner.sync_key(k, n1, n1), planner.sync_key(k, n1, tp_to), k
    )
    x = jnp.asarray(rng.normal(size=(n1, k + 1, 16)), jnp.float32)  # padded
    return x, tables


def _jnp_gather(xp, send_idx):
    return jax.vmap(lambda xr, ir: xr[ir])(xp, jnp.asarray(send_idx))


def test_pallas_interpret_flag_resolution(monkeypatch):
    from repro.kernels import mode

    # backend default: compiled on an accelerator, interpret on CPU
    assert ops.pallas_interpret() is (jax.default_backend() == "cpu")
    assert ops.pallas_interpret(True) is True       # explicit override wins
    assert ops.pallas_interpret(False) is False
    # on an accelerator only an explicit True interprets
    monkeypatch.setattr(mode.jax, "default_backend", lambda: "tpu")
    assert ops.pallas_interpret() is False
    assert ops.pallas_interpret(True) is True


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_kernel_interpret_matches_jnp(seed):
    x, tables = _case(seed)
    want = np.asarray(_jnp_gather(x, tables.send_idx))
    flat = x.reshape(x.shape[0], x.shape[1], -1)
    got = np.stack([
        np.asarray(ops.reshard_pack(flat[r], jnp.asarray(tables.send_idx[r]),
                                    interpret=True))
        for r in range(x.shape[0])
    ]).reshape(want.shape)
    assert np.array_equal(want, got)


def test_kernel_compiled_matches_jnp_or_skips():
    """Compiled mode: bit-identical to the jnp gather where the backend
    lowers Pallas."""
    x, tables = _case(3)
    flat = x.reshape(x.shape[0], x.shape[1], -1)
    idx = jnp.asarray(tables.send_idx[0])
    try:
        got = np.asarray(
            jax.block_until_ready(
                ops.reshard_pack(flat[0], idx, interpret=False)
            )
        )
    except Exception as e:  # pragma: no cover — CPU cannot lower Pallas TPU
        pytest.skip(f"backend {jax.default_backend()!r} cannot compile "
                    f"Pallas: {type(e).__name__}")
    want = np.asarray(_jnp_gather(x, tables.send_idx))[0].reshape(got.shape)
    assert np.array_equal(want, got)


def test_engine_kernel_route_matches_jnp_route():
    """`engine.reshard_ranks(use_kernel=True)` (the route `--use-kernel`
    serving and the state reshard take) == the pure-jnp route, bitwise —
    including the zero-pad slot semantics."""
    rng = np.random.default_rng(7)
    k, n1 = 6, 4
    tables = planner.tables(
        planner.sync_key(k, n1, 4), planner.sync_key(k, n1, 2), k
    )
    x = jnp.asarray(rng.normal(size=(n1, k, 3, 5)), jnp.float32)
    a = rse.reshard_ranks(x, tables, use_kernel=False)
    b = rse.reshard_ranks(x, tables, use_kernel=True)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    # and both agree with the numpy twin
    from repro.reshard.twin import emulate_tables

    c = emulate_tables(np.asarray(x), tables)
    assert np.array_equal(np.asarray(a), c)

