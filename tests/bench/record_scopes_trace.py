"""Record the trace that ``test_trace_scopes.py`` reads.

    python3 tests/bench/record_scopes_trace.py tests/bench/tpu_v5e_scopes.xplane.pb

On one accelerator: a tiny jitted training step whose loss has two named
scopes (``attention``, ``mlp``), taken with ``value_and_grad``, and whose
update, clipped by the gradients' global norm so that it cannot fuse into
the gradients' matmuls, runs under ``optimizer``; four times under
``bench.window``. Each step's input is made in a ``data.batch`` program
span (``repro.telemetry``, recorder off) that sleeps 2 ms inside
``bench.batch``; the step is dispatched inside
``StepTraceAnnotation("train", step_num=i)``, ``bench.step`` and a
``session.step`` span, and waited for in ``bench.wait``.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

STEPS = 4
N = 256


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import telemetry

    if jax.devices()[0].platform == "cpu":
        raise SystemExit("needs an accelerator: the trace's device plane is "
                         "what the tests read")

    def loss(w, x):
        with jax.named_scope("attention"):
            q, k = x @ w["q"], x @ w["k"]
            h = jax.nn.softmax(q @ k.T / N ** 0.5, axis=-1) @ x
        with jax.named_scope("mlp"):
            y = jax.nn.gelu(h @ w["up"]) @ w["down"]
        return jnp.mean(y * y)

    @jax.jit
    def step(w, x):
        value, grads = jax.value_and_grad(loss)(w, x)
        with jax.named_scope("optimizer"):
            norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
            lr = 1e-3 / jnp.maximum(1.0, norm)
            w = jax.tree.map(lambda p, g: p - lr * g, w, grads)
        return w, value

    rng = np.random.default_rng(0)
    w = {k: jnp.asarray(rng.standard_normal((N, N), np.float32) / N ** 0.5)
         for k in ("q", "k", "up", "down")}
    tel = telemetry.get()
    ann = jax.profiler.TraceAnnotation

    def batch():
        with tel.span("data.batch"):
            time.sleep(0.002)
            return jnp.asarray(rng.standard_normal((N, N), np.float32))

    jax.block_until_ready(step(w, batch()))   # compile outside the trace
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with ann("bench.window"):
        for i in range(STEPS):
            with ann("bench.batch"):
                x = batch()
            with jax.profiler.StepTraceAnnotation("train", step_num=i), \
                    ann("bench.step"), tel.span("session.step"):
                w, value = step(w, x)
            with ann("bench.wait"):
                jax.block_until_ready(value)
    jax.profiler.stop_trace()
    (found,) = Path(tmp).glob("plugins/profile/*/*.xplane.pb")
    shutil.copy(found, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {out} ({Path(out).stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
