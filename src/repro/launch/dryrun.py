import os
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512")))
# ^ MUST run before any jax import: jax locks the device
#   count on first init. 512 placeholder host devices back the production
#   meshes (16×16 single-pod, 2×16×16 multi-pod). Never set this globally —
#   smoke tests and benches see 1 device.
"""Multi-pod dry-run driver.

For every (arch × input-shape × mesh):
  jax.jit(step, in_shardings, out_shardings).lower(**input_specs).compile()
then record memory_analysis(), cost_analysis(), and the collective schedule
parsed from the optimized HLO, into results/dryrun/*.json — the source data
for EXPERIMENTS.md §Dry-run / §Roofline and benchmarks/roofline.py.
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Dict, List

import numpy as np

import jax

from repro.configs import SHAPES, all_archs, get_arch, get_shape
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.train.steps import make_setup

# ---------------------------------------------------------------------------
# target hardware constants (TPU v5e-like, per chip)
PEAK_FLOPS = 197e12     # bf16 FLOP/s
HBM_BW = 819e9          # bytes/s
LINK_BW = 50e9          # bytes/s per ICI link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0, "opaque": 0,
}

_COLL_RE = re.compile(
    r"=\s+(?P<rtype>\([^)]*\)|[a-z0-9]+\[[^\]]*\][^\s]*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<start>-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_ITOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")


def _bytes_of(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> List[dict]:
    """Per-device moved-bytes estimate for each collective (ring formulas)."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        rbytes = _bytes_of(m.group("rtype"))
        gi = _GROUPS_ITOTA_RE.search(line)
        if gi:
            n = int(gi.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(line)
            n = len(gl.group(1).split(",")) if gl else 1
        if n <= 1:
            moved = 0.0
        elif op == "all-reduce":
            moved = 2.0 * rbytes * (n - 1) / n
        elif op == "all-gather":
            moved = rbytes * (n - 1) / n
        elif op == "reduce-scatter":
            moved = float(rbytes) * (n - 1)
        elif op == "all-to-all":
            moved = rbytes * (n - 1) / n
        else:  # collective-permute
            moved = float(rbytes)
        out.append({"op": op, "result_bytes": rbytes, "group": n, "moved_bytes": moved})
    return out


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), N = active params."""
    n = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def run_one(arch_id: str, shape_name: str, multi_pod: bool, unroll: bool = False) -> Dict:
    cfg = get_arch(arch_id)
    shape = get_shape(shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: Dict = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "ok": False,
    }
    if shape.name == "long_500k" and not cfg.supports_long_decode:
        rec.update(skipped=True, reason=cfg.long_decode_note)
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    su = make_setup(cfg, shape, mesh, dp_axes=dp_axes(mesh), scan_unroll=unroll)
    with mesh:
        step = su.jit_step()
        lowered = step.lower(*su.abstract_args())
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis()
    if isinstance(xla_cost, (list, tuple)):
        xla_cost = xla_cost[0]
    # loop-aware analysis (launch/hlo_analysis.py): XLA's cost_analysis counts
    # while bodies once; ours multiplies by known_trip_count.
    hlo = analyze_hlo(compiled.as_text())

    flops_dev = float(hlo["flops"])
    bytes_dev = float(hlo["bytes"])
    coll_dev = float(hlo["collective_moved_bytes"])
    mflops = model_flops(cfg, shape)

    t_comp = flops_dev / PEAK_FLOPS
    t_mem = bytes_dev / HBM_BW
    t_coll = coll_dev / LINK_BW
    dom = max((t_comp, "compute"), (t_mem, "memory"), (t_coll, "collective"))[1]

    per_type = hlo["collectives"]

    rec.update(
        ok=True,
        chips=chips,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        memory={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "generated_code_bytes": mem.generated_code_size_in_bytes,
        },
        hlo_flops_per_device=flops_dev,
        hlo_bytes_per_device=bytes_dev,
        collective_bytes_per_device=coll_dev,
        collectives=per_type,
        unknown_trip_count_loops=hlo["unknown_trip_count_loops"],
        xla_cost_flops=float(xla_cost.get("flops", 0.0)),
        xla_bytes_accessed=float(xla_cost.get("bytes accessed", 0.0)),
        roofline={
            "compute_s": t_comp,
            "memory_s": t_mem,
            "collective_s": t_coll,
            "dominant": dom,
        },
        model_flops_total=mflops,
        model_flops_per_device=mflops / chips,
        useful_flops_ratio=(mflops / chips) / flops_dev if flops_dev else None,
        n_params=cfg.n_params(),
        n_active_params=cfg.n_active_params(),
    )
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--print-hlo-collectives", action="store_true")
    args = ap.parse_args()

    archs = list(all_archs()) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                path = os.path.join(args.out, f"{arch}__{shape}__{mesh_name}.json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip existing] {path}")
                    continue
                print(f"=== dryrun {arch} × {shape} × {mesh_name}", flush=True)
                try:
                    rec = run_one(arch, shape, mp)
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = {
                        "arch": arch, "shape": shape, "mesh": mesh_name,
                        "ok": False, "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-2000:],
                    }
                    failures += 1
                    print(rec["error"], file=sys.stderr, flush=True)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                if rec.get("ok"):
                    r = rec["roofline"]
                    print(
                        f"  ok: compute {r['compute_s']*1e3:.2f}ms  memory "
                        f"{r['memory_s']*1e3:.2f}ms  collective {r['collective_s']*1e3:.2f}ms"
                        f"  dominant={r['dominant']}  useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'],3)}",
                        flush=True,
                    )
                elif rec.get("skipped"):
                    print(f"  skipped: {rec['reason']}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
