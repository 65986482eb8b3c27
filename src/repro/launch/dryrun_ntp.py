import os
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512")))
# ^ first lines, before any jax import (see dryrun.py).
"""NTP-mode dry-run: lower the nonuniform-TP train step at the production
mesh (data=16 × model=16) with degraded replicas, and account the reshard
collectives from the optimized HLO — the paper's Fig. 9 overhead breakdown
derived structurally.

  PYTHONPATH=src python -m repro.launch.dryrun_ntp [--replica-tp 16,...,14]
"""
import argparse
import json

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import ntp_train as nt
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.dryrun import LINK_BW, PEAK_FLOPS
from repro.optim import sgd
from repro.runtime import ClusterHealth, Mode, plan_from_health


def build_cfg(d_model: int = 6144) -> nt.NTPModelConfig:
    # the paper's §5.1 prototype dims (hidden 6144, head dim 128, ffn 4x)
    return nt.NTPModelConfig(
        d_model=d_model,
        n_kv_groups=16, q_per_kv=3 if d_model == 6144 else 6, head_dim=128,
        d_ff=4 * d_model,
        unit_rows=128,
        n_layers=2, vocab=32000,
    )


def run(replica_tp, *, d_model: int = 6144, local_batch: int = 1, seq: int = 2048,
        mesh_shape=(16, 16)):
    import math
    n = math.prod(mesh_shape)
    mesh = jax.make_mesh(mesh_shape, ("data", "model"),
                         devices=jax.devices()[:n])
    cfg = build_cfg(d_model)
    # event/health bridge: replica_tp -> per-domain failures -> packed plan
    health = ClusterHealth(
        domain_size=mesh_shape[1],
        failed=tuple(mesh_shape[1] - t for t in replica_tp),
    )
    fplan = plan_from_health(health)
    mode = Mode.UNIFORM if fplan.healthy else Mode.NTP
    optimizer = sgd(1e-2)  # memory-neutral: dryrun reports temp bytes
    step = nt.make_ntp_train_step(
        cfg, fplan, mesh, mode=mode, local_batch=local_batch,
        optimizer=optimizer,
    )
    canon_shapes = jax.eval_shape(
        lambda k: nt.init_canonical(cfg, k), jax.random.PRNGKey(0)
    )
    # abstract packed params: same structure, packed shapes
    canon = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), canon_shapes
    )
    packed = nt.pack_params(cfg, canon, fplan)
    tokens = jax.ShapeDtypeStruct((mesh_shape[0] * local_batch, seq + 1), jnp.int32)
    packed_abs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), packed
    )
    opt_abs = jax.eval_shape(optimizer.init, packed_abs)
    lowered = step.lower(packed_abs, opt_abs, tokens)
    compiled = lowered.compile()
    hlo = analyze_hlo(compiled.as_text())
    a2a = hlo["collectives"].get("all-to-all", {"count": 0, "moved_bytes": 0})
    ar = hlo["collectives"].get("all-reduce", {"count": 0, "moved_bytes": 0})
    return {
        "replica_tp": list(fplan.replica_tp),
        "mode": mode.value,
        "flops_per_device": hlo["flops"],
        "compute_s": hlo["flops"] / PEAK_FLOPS,
        "all_to_all": a2a,
        "all_reduce": ar,
        "reshard_s": a2a["moved_bytes"] / LINK_BW,
        "allreduce_s": ar["moved_bytes"] / LINK_BW,
        "collectives": hlo["collectives"],
        "memory_temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--degraded-tp", type=int, default=14)
    ap.add_argument("--d-model", type=int, default=6144)
    ap.add_argument("--mesh", default="16x16",
                    help="data x model, e.g. 4x8 (compiles much faster)")
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--local-batch", type=int, default=1)
    ap.add_argument("--out", default="results/ntp_dryrun.json")
    args = ap.parse_args()

    md, mm = (int(t) for t in args.mesh.split("x"))
    kw = dict(d_model=args.d_model, mesh_shape=(md, mm), seq=args.seq,
              local_batch=args.local_batch)
    healthy = run([mm] * md, **kw)
    degraded = run([args.degraded_tp] + [mm] * (md - 1), **kw)
    delta_ar = degraded["allreduce_s"] - healthy["allreduce_s"]
    report = {
        "healthy": healthy,
        "degraded": degraded,
        "overhead": {
            "reshard_s": degraded["reshard_s"],
            "allreduce_increase_s": delta_ar,
            "reshard_vs_compute": degraded["reshard_s"] / degraded["compute_s"],
            "note": "paper Fig. 9: reshard overlaps backward; all-reduce "
                    "volume grows ∝ TP reduction; both <1% e2e",
        },
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report["overhead"], indent=1))
    print(f"healthy: ar={healthy['allreduce_s']*1e3:.1f}ms a2a={healthy['reshard_s']*1e3:.2f}ms "
          f"compute={healthy['compute_s']*1e3:.1f}ms")
    print(f"degraded: ar={degraded['allreduce_s']*1e3:.1f}ms a2a={degraded['reshard_s']*1e3:.2f}ms "
          f"compute={degraded['compute_s']*1e3:.1f}ms")


if __name__ == "__main__":
    main()
