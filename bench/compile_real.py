"""Compile each cell's programs at their real size for a described TPU v5e,
without the chip, and print the bytes each needs on the device
(``compiled.memory_analysis()``): a training cell's step, a serving cell's
slot decode and prefill, with the bytes a serving engine holds between them
(weights and the slot cache).

    JAX_PLATFORMS=cpu python3 bench/compile_real.py [--cell NAME] [--layers N]
        [--seq N]

The options override the cell's depth or sequence, to find the largest
that fits. Nothing runs; a compile that passes is not a chip run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default=None)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    spec = harness.load_spec()
    names = [args.cell] if args.cell else [w["name"] for w in spec["workloads"]]
    for name in names:
        cell = harness.load_cell(name, spec)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        if cell.traffic["driver"] == "train_closed_loop":
            res = train(cell, one, args.layers, args.seq)
        elif cell.traffic["driver"] == "serve_open_loop":
            res = serve(cell, one, args.layers)
        else:
            raise SystemExit(f"{name}: no programs to compile for driver "
                             f"{cell.traffic['driver']!r}")
        print(json.dumps({"cell": name, "device": topo.devices[0].device_kind,
                          **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
