"""Closed-loop training through ``NTPSession.from_arch(...).step`` (the
``launch/train.py --arch`` path): batches from ``SyntheticLMPipeline.batch``
as the launcher builds them, AdamW, float32 weights.

Traffic parameters: ``batch``, ``seq``, ``in_flight`` (steps the host may
run ahead of the device), ``check_steps`` (the first steps, driven in
set-up through the window's own call and feed, that the reference follows).

Set-up builds the session, gives it weights made from the seed, and drives
it through the check steps: the loss of each, the gradient norms of the
first (from AdamW's first moment after it) and the norms of the parameters'
change after the last are kept. The window then goes on with the same
session. Once it has closed and the session is freed, the reference
repeats the check steps on the same tokens from the same weights.
"""
from __future__ import annotations

import gc
import sys
import time
from collections import deque
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench import harness  # noqa: E402
from bench import reference as R  # noqa: E402
from bench.harness import Check, Outcome, span  # noqa: E402


def setup(run):
    import jax

    from repro.configs.shapes import ShapeSpec
    from repro.data.pipeline import DataConfig, SyntheticLMPipeline
    from repro.optim import AdamWConfig
    from repro.runtime import NTPSession

    s, tr, C = run.cell.sizes, run.cell.traffic, run.cell.config
    o = s["optimizer"]
    cfg = C.arch_config(s)
    key = jax.random.PRNGKey(harness.seed32(run.seed, 1))
    opt_cfg = AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                          weight_decay=o["weight_decay"],
                          grad_clip=o["grad_clip"])
    session = NTPSession.from_arch(
        cfg, ShapeSpec("bench", tr["seq"], tr["batch"], "train"),
        opt_cfg=opt_cfg, key=key)
    # from_arch initialises its own weights from a key; the benchmark's
    # come from the seed, made here, so that the reference can make the
    # same ones without reading the program's
    session._params = None
    gc.collect()
    vp = cfg.padded_vocab()
    make = jax.jit(lambda k: C.to_program(s, C.init(s, k), vp))
    session._params = make(key)
    pipe = SyntheticLMPipeline(DataConfig(cfg.vocab_size, tr["seq"],
                                          tr["batch"], seed=run.seed))
    return {"session": session, "pipe": pipe, "key": key, "padded_vocab": vp}


def check_steps(run, st):
    """The first steps, through the window's own call and feed."""
    import jax

    C, s = run.cell.config, run.cell.sizes
    session, pipe = st["session"], st["pipe"]
    norms = jax.jit(lambda t: C.program_norms(s, t))
    b1 = s["optimizer"]["b1"]
    rec = {"tokens": [], "targets": [], "loss": []}
    for i in range(run.cell.traffic["check_steps"]):
        b = pipe.batch(i)
        rec["tokens"].append(np.asarray(b["tokens"]))
        rec["targets"].append(np.asarray(b["targets"]))
        metrics = session.step(b)
        rec["loss"].append(float(metrics["loss"]))
        if i == 0:
            m = jax.device_get(norms(session.opt_state["m"]))
            rec["grad"] = {k: float(v) / (1 - b1) for k, v in m.items()}
    # the initial weights are made again inside the program that takes the
    # difference, so that no second copy of them has to fit beside the
    # session's state
    vp = st["padded_vocab"]
    change = jax.jit(lambda p, k: C.program_change_norms(
        s, p, C.to_program(s, C.init(s, k), vp)))
    rec["change"] = {k: float(v) for k, v in
                     jax.device_get(change(session.params, st["key"])).items()}
    st["next_step"] = run.cell.traffic["check_steps"]
    return rec


def window(run, st):
    import jax

    tr = run.cell.traffic
    session, pipe = st["session"], st["pipe"]
    pending = deque()
    input_s, steps = [], 0
    i = st["next_step"]
    pauses = harness.HostPauses()
    with run.traced():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < run.seconds:
            with span("bench.batch"), pauses.part("batch"):
                tb = time.perf_counter()
                b = pipe.batch(i)
                input_s.append(time.perf_counter() - tb)
            with span("bench.step"), pauses.part("step"):
                metrics = session.step(b)
            pending.append(metrics["loss"])
            steps += 1
            i += 1
            if len(pending) > tr["in_flight"]:
                with span("bench.wait"), pauses.part("wait"):
                    jax.block_until_ready(pending.popleft())
        with span("bench.wait"):
            jax.block_until_ready((session.params, list(pending)))
        t1 = time.perf_counter()
    tokens = steps * tr["batch"] * tr["seq"]
    return {"steps": steps, "tokens": tokens, "window_s": t1 - t0,
            "t0": t0, "t1": t1, "input_s": input_s,
            "host": pauses.close(),
            "last_loss": float(pending[-1]) if pending else None}


def reference(run, rec, *, dtype=None, rows=None):
    """The reference's check numbers on the same tokens, from the same
    weights, in float32 at ``highest`` matmul precision; or, for the
    control, everything in ``dtype`` at the precision the configuration
    states: weights, activations, gradients, the optimizer's state and
    each update. ``rows`` keeps only those rows of each batch (a planted
    fault: half of the batch left out)."""
    import jax
    import jax.numpy as jnp

    C, s = run.cell.config, run.cell.sizes
    dtype = dtype or jnp.float32
    key = jax.random.PRNGKey(harness.seed32(run.seed, 1))
    opt = C.optimizer(s)
    cast = lambda t: jax.tree.map(lambda x: x.astype(dtype), t)
    prec = "highest" if dtype == jnp.float32 else s["matmul_precision"]
    with jax.default_matmul_precision(prec):
        w_init = jax.jit(lambda k: cast(C.init(s, k)))(key)
        w = jax.tree.map(lambda x: x + 0, w_init)
        state = jax.jit(lambda w: cast(R.adamw_init(w)))(w)

        @jax.jit
        def step(w, state, tok, tgt, t):
            l, g = jax.value_and_grad(
                lambda w: C.loss(s, w, tok, tgt, dtype))(w)
            w2, state2, clipped = opt(w, state, g, t)
            return cast(w2), cast(state2), l, C.reference_norms(clipped)

        out = {"loss": []}
        for t, (tok, tgt) in enumerate(zip(rec["tokens"], rec["targets"])):
            if rows is not None:
                tok, tgt = tok[rows], tgt[rows]
            w, state, l, gn = step(w, state, tok, tgt, t)
            out["loss"].append(float(l))
            if t == 0:
                out["grad"] = {k: float(v) for k, v in
                               jax.device_get(gn).items()}
        diff = jax.jit(lambda a, b: C.reference_norms(
            jax.tree.map(lambda x, y: x.astype(jnp.float32)
                         - y.astype(jnp.float32), a, b)))(w, w_init)
        out["change"] = {k: float(v) for k, v in jax.device_get(diff).items()}
    return out


def compare(prog, ref):
    """The three numbers: the largest loss gap over the check steps, the
    worst leaf of the first gradient's norms, and the worst moving leaf of
    the parameters' change after the check steps."""
    keep = harness.moving_leaves(ref["grad"])
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["loss"], ref["loss"])),
        "grad_norm_gap": harness.leaf_gap(prog["grad"], ref["grad"]),
        "change_norm_gap": harness.leaf_gap(prog["change"], ref["change"],
                                            keep),
    }


def readings(run, rec, ref):
    """The control and the planted faults, read against the reference."""
    import jax.numpy as jnp

    half = list(range(rec["tokens"][0].shape[0] // 2))
    return {"control_bf16": compare(reference(run, rec, dtype=jnp.bfloat16),
                                    ref),
            "half_batch": compare(reference(run, rec, rows=half), ref)}


def run(run):
    lim = harness.load_limits(run)
    st = setup(run)
    rec = check_steps(run, st)
    run.setup_done()
    win = window(run, st)
    mem = harness.memory_peak_bytes(run.devices)
    st.clear()
    gc.collect()
    ref = reference(run, rec)
    nums = compare(rec, ref)
    checks = [Check(k, v, lim[k]) for k, v in nums.items()]
    e2e = {"setup_s": run.setup_s,
           "train_tokens_per_s": win["tokens"] / win["window_s"]}
    print(f"train window: {win['steps']} steps; "
          + harness.HostPauses.describe(win["host"]), file=sys.stderr)
    records = {"window": win, "check": {"program": rec, "reference": ref},
               "setup_compile_s": run.setup_compile_s}
    if run.readings:
        records["readings"] = readings(run, rec, ref)
    return Outcome(attempted=win["steps"], failed=0, end_to_end=e2e,
                   records=records, checks=checks, memory_peak_bytes=mem)
