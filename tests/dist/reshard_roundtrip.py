"""Reshard collective: comp->sync->comp round-trips exactly, and the full
NTP gradient sync equals the cross-replica unit sum. 8 fake CPU devices
(XLA_FLAGS set by the test runner)."""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core import nonuniform as nu
from repro.core import reshard as rs

K, UNIT = 11, 6
mesh = jax.make_mesh((2, 4), ("data", "model"))
plan = nu.FailurePlan(n1=4, replica_tp=(3, 4))
wp = nu.weight_plan(K, plan)

rng = np.random.default_rng(0)
canon = rng.standard_normal((K, UNIT)).astype(np.float32)
packed = jnp.asarray(nu.pack_global(canon, wp, 1))  # (D, n1*buf, 1, UNIT)
spec = P("data", "model")


def roundtrip(x):
    x = x.reshape(x.shape[1:])          # drop the replica block dim
    y = rs.reshard(x, wp.pre)
    y = rs.reshard(y, wp.post)
    return y.reshape((1,) + y.shape)


out = shard_map(roundtrip, mesh=mesh, in_specs=(spec,), out_specs=spec,
                check_vma=False)(packed)
assert np.allclose(np.asarray(out), np.asarray(packed)), "roundtrip mismatch"
for r in range(plan.d):
    got = nu.unpack_global(np.asarray(out), wp, 1, replica=r)
    assert np.allclose(got, canon), f"replica {r} units corrupted"
print("roundtrip exact on both replicas")


def scaled_sync(x):
    # give each replica a distinct contribution: replica d scales by (d+1)
    d = jax.lax.axis_index("data")
    x = x.reshape(x.shape[1:]) * (d + 1).astype(x.dtype)
    y = rs.ntp_sync_gradient(x, wp)
    return y.reshape((1,) + y.shape)


synced = shard_map(scaled_sync, mesh=mesh, in_specs=(spec,), out_specs=spec,
                   check_vma=False)(packed)
expect = canon * sum(d + 1 for d in range(plan.d))  # 1x + 2x = 3x
for r in range(plan.d):
    got = nu.unpack_global(np.asarray(synced), wp, 1, replica=r)
    assert np.allclose(got, expect, atol=1e-5), f"replica {r} sync wrong"
print("ntp_sync_gradient == cross-replica unit sum on every replica")
print("RESHARD_OK")
