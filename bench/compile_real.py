"""Compile each cell's programs at their real size for a described TPU v5e,
without the chip, and print the bytes each needs on the device
(``compiled.memory_analysis()``): a training cell's step, a serving cell's
slot decode and prefill, with the bytes a serving engine holds between them
(weights and the slot cache), an NTP cell's step in the healthy and in the
degraded layout on a described v5e:2x2 (bytes on each device).

    JAX_PLATFORMS=cpu python3 bench/compile_real.py [--cell NAME] [--layers N]
        [--seq N] [--local-batch N]

The options override the cell's depth, sequence or (NTP) local batch, to
find the largest that fits. A cell that ``BENCHMARK.json`` does not list
yet is read from ``bench/queued/<cell>.json``, which holds its entries.
Nothing runs; a compile that passes is not a chip run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

GB = 1e9


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: getattr(m, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes", "alias_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes")}
    out["total_gb"] = (out["argument_size_in_bytes"] + out["output_size_in_bytes"]
                       - out["alias_size_in_bytes"]
                       + out["temp_size_in_bytes"]) / GB
    return out


def _on(tree, sharding):
    import jax

    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=sharding), tree)


def train(cell, dev, layers=None, seq=None):
    import jax
    import jax.numpy as jnp

    from repro.configs.shapes import ShapeSpec
    from repro.optim import AdamWConfig
    from repro.train.steps import make_setup

    s = dict(cell.sizes)
    if layers:
        s["num_hidden_layers"] = layers
    tr = cell.traffic
    cfg = cell.config.arch_config(s)
    su = make_setup(cfg, ShapeSpec("bench", seq or tr["seq"], tr["batch"],
                                   "train"),
                    None, param_dtype=jnp.float32,
                    opt_cfg=AdamWConfig(lr=s["optimizer"]["lr"]))
    args = _on(su.abstract_args(), dev)
    return {"train_step": _mem(su.jit_step().lower(*args).compile())}


def serve(cell, dev, layers=None):
    import jax
    import jax.numpy as jnp

    from repro.models.transformer import build_model

    s = dict(cell.sizes)
    if layers:
        s["num_hidden_layers"] = layers
    tr, C = cell.traffic, cell.config
    cfg = C.arch_config(s)
    model = build_model(cfg, remat=False)
    f32 = jnp.float32
    params = _on(jax.eval_shape(
        lambda k: C.to_program(s, C.init(s, k), cfg.padded_vocab()),
        jax.random.PRNGKey(0)), dev)
    slots = tr["slots"]
    cache = _on(jax.eval_shape(lambda: model.init_slot_cache(
        slots, tr["max_len"], f32)), dev)
    cache1 = _on(jax.eval_shape(lambda: model.init_cache(
        1, tr["max_len"], f32)), dev)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=dev)
    nbytes = lambda t: sum(x.size * x.dtype.itemsize
                           for x in jax.tree.leaves(t))
    decode = jax.jit(model.decode_slots).lower(
        params, cache, i32(slots), i32(slots)).compile()
    prefill = jax.jit(model.prefill).lower(
        params, i32(1, tr["prefill_len"]), cache1).compile()
    return {"layers": s["num_hidden_layers"],
            "weights_gb": nbytes(params) / GB,
            "slot_cache_gb": nbytes(cache) / GB,
            "decode_slots": _mem(decode), "prefill": _mem(prefill)}


def ntp(cell, topo, layers=None, seq=None, local_batch=None):
    """The NTP step of each layout the cell's window runs, on a mesh of the
    described chips, with its arguments placed as the session places them
    (unit buffers split over data and model, the rest replicated)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import ntp_train as nt
    from repro.core.nonuniform import FailurePlan, weight_plan
    from repro.optim import AdamWConfig, adamw

    s = dict(cell.sizes)
    if layers:
        s["num_hidden_layers"] = layers
    tr = dict(cell.traffic)
    tr["seq"] = seq or tr["seq"]
    tr["local_batch"] = local_batch or tr["local_batch"]
    C, D = cell.config, cell.driver
    cfg = C.model_config(s)
    d, n1 = tr["data"], tr["model"]
    mesh = Mesh(np.array(topo.devices[: d * n1]).reshape(d, n1),
                ("data", "model"))
    canonical = jax.eval_shape(lambda k: C.init(s, k), jax.random.PRNGKey(0))
    opt = adamw(AdamWConfig(lr=s["optimizer"]["lr"]))
    out = {"layers": s["num_hidden_layers"], "seq": tr["seq"],
           "local_batch": tr["local_batch"]}
    for name, tp in zip(("healthy", "degraded"), D.expected_plans(tr)):
        plan = FailurePlan(n1=n1, replica_tp=tp)
        bufs = {"attn": weight_plan(cfg.n_kv_groups, plan).buf,
                "mlp": weight_plan(cfg.k_ff, plan).buf}

        def packed(path, x):
            key = path[-1].key if hasattr(path[-1], "key") else None
            if key in nt.UNIT_KEYS:
                buf = bufs["attn" if key.startswith("w") else "mlp"]
                return jax.ShapeDtypeStruct(
                    (d, n1 * buf) + x.shape[1:], x.dtype,
                    sharding=NamedSharding(mesh, P("data", "model")))
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=NamedSharding(mesh, P()))

        params = jax.tree_util.tree_map_with_path(packed, canonical)
        state = {"m": params, "v": params, "step": jax.ShapeDtypeStruct(
            (), jnp.int32, sharding=NamedSharding(mesh, P()))}
        batch = jax.ShapeDtypeStruct(
            (d * tr["local_batch"], tr["seq"] + 1), jnp.int32,
            sharding=NamedSharding(mesh, P("data", None)))
        step = nt.make_ntp_train_step(cfg, plan, mesh,
                                      local_batch=tr["local_batch"],
                                      optimizer=opt)
        out[name] = {"plan": list(tp),
                     **_mem(step.lower(params, state, batch).compile())}
    # the reference's step, on one chip at highest precision
    dev0 = jax.sharding.SingleDeviceSharding(topo.devices[0])
    on0 = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=dev0)
    w = jax.tree.map(lambda x: on0(x.shape, x.dtype), canonical)
    rows = d * tr["local_batch"]
    run = SimpleNamespace(cell=SimpleNamespace(config=C, sizes=s,
                                               traffic=tr))
    with jax.default_matmul_precision("highest"):
        ref = D.reference_step(run, jnp.float32).lower(
            w, {"m": w, "v": w}, on0((rows, tr["seq"] + 1), jnp.int32),
            on0((rows,), jnp.float32), on0((), jnp.int32)).compile()
    out["reference"] = _mem(ref)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default=None)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--local-batch", type=int, default=None)
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    spec = harness.load_spec()
    names = [args.cell] if args.cell else [w["name"] for w in spec["workloads"]]
    for name in names:
        queued = harness.BENCH / "queued" / f"{name}.json"
        listed = any(w["name"] == name for w in spec["workloads"])
        cell = harness.load_cell(name, spec if listed or not queued.is_file()
                                 else json.loads(queued.read_text()))
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        if cell.traffic["driver"] == "train_closed_loop":
            res = train(cell, one, args.layers, args.seq)
        elif cell.traffic["driver"] == "serve_open_loop":
            res = serve(cell, one, args.layers)
        elif cell.traffic["driver"] == "ntp_fail_repair":
            res = ntp(cell, topo, args.layers, args.seq, args.local_batch)
        else:
            raise SystemExit(f"{name}: no programs to compile for driver "
                             f"{cell.traffic['driver']!r}")
        print(json.dumps({"cell": name, "device": topo.devices[0].device_kind,
                          **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
