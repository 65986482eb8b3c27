"""Smoke run of the system's main paths on a TPU, through the entry points
the launchers use.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # four chips: the NTP fail/repair path only

One chip runs four phases at the published widths of granite-3-2b (depth
cut, random weights from ``--seed``):

* train/arch   ``NTPSession.from_arch`` (the ``launch/train.py --arch`` path)
               for a few AdamW steps; first loss within 1 of ln(vocab);
* train/ntp    ``NTPSession.create`` on a data=1 x model=1 mesh: the
               shard_map NTP step through the TPU compiler;
* serve        ``ServeSession`` + ``Router`` (the ``launch/serve.py --full``
               path, at its default matmul precision): one failure and one
               repair mid-decode, every greedy stream equal to a run with
               no events;
* kernels      every Pallas kernel through ``kernels.ops``, dispatched
               compiled, against its jnp reference.

Four chips run NTP training on a data=2 x model=2 mesh through
healthy -> FailureEvent -> plan (1, 2) -> RecoveryEvent -> (2, 2), replayed
by ``TraceRunner(verify=True)`` against the dense single-copy reference.

Findings go to earlier lines; the last line is one JSON object naming the
device. Step and compile times are timings of this one run, not a
benchmark. Without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "granite-3-2b"
TRAIN_LAYERS = 4       # depth cut (published: 40)
NTP4_LAYERS = 2        # four-chip cut: compile time is paid on four chips
TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = 2048, 4, 5
NTP_SEQ, NTP_LOCAL_BATCH = 512, 4    # the prototype materialises S x S scores
SERVE_REQUESTS, SERVE_PROMPT, SERVE_NEW = 8, 128, 32
LOSS_ATOL = 1e-4       # the dist lifecycle tests' dense-reference tolerance


class CompileMeter:
    """Sums JAX's own compile-time events (backend compile, persistent
    cache hits) so each phase can report how long it spent compiling."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, h0, t0 = self.compile_s, self.hits, time.perf_counter()
        print(f"== {name}", flush=True)
        yield
        print(f"== {name}: ok  wall {time.perf_counter() - t0:.2f} s  "
              f"compile {self.compile_s - c0:.2f} s  "
              f"persistent-cache hits {self.hits - h0}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def granite(n_layers: int = TRAIN_LAYERS):
    from repro.configs import get_arch

    full = get_arch(ARCH)
    return full, dataclasses.replace(full, n_layers=n_layers)


def ntp_config(arch_cfg):
    """The NTP prototype at the arch's widths: one kv-group unit per KV
    head, 128-row MLP units. Its MLP is a plain GELU (not gated) and its
    head is untied — the prototype's own block structure."""
    from repro.runtime import NTPModelConfig

    return NTPModelConfig(
        d_model=arch_cfg.d_model, n_kv_groups=arch_cfg.n_kv_heads,
        q_per_kv=arch_cfg.n_heads // arch_cfg.n_kv_heads,
        head_dim=arch_cfg.head_dim, d_ff=arch_cfg.d_ff, unit_rows=128,
        n_layers=arch_cfg.n_layers, vocab=arch_cfg.vocab_size,
    )


# --------------------------------------------------------------------- phases

def train_arch(cfg, *, seq: int, batch: int, steps: int, seed: int):
    import jax

    from repro.configs.shapes import ShapeSpec
    from repro.data.pipeline import DataConfig, SyntheticLMPipeline
    from repro.optim import AdamWConfig
    from repro.runtime import NTPSession

    session = NTPSession.from_arch(
        cfg, ShapeSpec("smoke", seq, batch, "train"),
        opt_cfg=AdamWConfig(lr=3e-4), key=jax.random.PRNGKey(seed))
    n_par = sum(p.size for p in jax.tree.leaves(session.params))
    print(f"train/arch: {n_par / 1e6:.1f}M params, batch {batch} x seq {seq}")
    pipe = SyntheticLMPipeline(DataConfig(cfg.vocab_size, seq, batch,
                                          seed=seed))
    losses = []
    for i in range(steps):
        b = pipe.batch(i)
        t0 = time.perf_counter()
        metrics = session.step(b)
        jax.block_until_ready((session.params, metrics))
        dt = time.perf_counter() - t0
        losses.append(float(metrics["loss"]))
        print(f"train/arch step {i}: loss {losses[-1]:.5f}  "
              f"grad_norm {float(metrics['grad_norm']):.4f}  "
              f"smoke wall {dt:.3f} s"
              + ("  (includes compile)" if i == 0 else ""), flush=True)
    check(all(math.isfinite(x) for x in losses),
          f"train/arch: non-finite loss in {losses}")
    ln_v = math.log(cfg.vocab_size)
    check(abs(losses[0] - ln_v) < 1.0,
          f"train/arch: first loss {losses[0]:.4f} not within 1 of "
          f"ln(vocab) = {ln_v:.4f}")
    print(f"train/arch: first loss {losses[0]:.5f} vs ln(vocab) {ln_v:.5f}")
    return losses


def train_ntp(ncfg, mesh, *, seq: int, local_batch: int, steps: int,
              seed: int):
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import DataConfig, SyntheticLMPipeline
    from repro.optim import AdamWConfig, adamw
    from repro.runtime import NTPSession

    session = NTPSession.create(
        ncfg, mesh, local_batch=local_batch,
        optimizer=adamw(AdamWConfig(lr=3e-4)), key=jax.random.PRNGKey(seed))
    d = mesh.shape["data"]
    pipe = SyntheticLMPipeline(DataConfig(ncfg.vocab, seq, d * local_batch,
                                          seed=seed))
    print(f"train/ntp: mesh data={d} model={mesh.shape['model']} plan "
          f"{session.plan}, batch {d * local_batch} x seq {seq}")
    losses = []
    for i in range(steps):
        batch = jnp.asarray(pipe._batch_np(i))
        t0 = time.perf_counter()
        metrics = session.step(batch)
        jax.block_until_ready((session.params, metrics))
        dt = time.perf_counter() - t0
        losses.append(float(metrics["loss"]))
        print(f"train/ntp step {i}: loss {losses[-1]:.5f}  "
              f"grad_norm {float(metrics['grad_norm']):.4f}  "
              f"smoke wall {dt:.3f} s"
              + ("  (includes compile)" if i == 0 else ""), flush=True)
    check(all(math.isfinite(x) for x in losses),
          f"train/ntp: non-finite loss in {losses}")
    return losses


def serve_failover(cfg, *, requests: int, prompt_len: int, max_new: int,
                   fail_tick: int, repair_tick: int, seed: int):
    """Greedy streams through one failure and one repair equal the streams
    of a run with no events (the ``examples/serve_failover.py`` check)."""
    import jax
    import numpy as np

    from repro.runtime import FailureEvent, RecoveryEvent
    from repro.serve import Request, Router, ServeSession

    def run(events):
        session = ServeSession.create(
            cfg, replicas=1, n1=4, slots=requests,
            max_len=prompt_len + max_new, prefill_len=prompt_len,
            policy="ntp", key=jax.random.PRNGKey(seed))
        router = Router(session)
        rng = np.random.default_rng(seed)
        for i in range(requests):
            router.submit(Request(
                rid=i, max_new=max_new,
                prompt=rng.integers(1, cfg.vocab_size,
                                    size=prompt_len).astype(np.int32)))
        engine, tick = session.engines[0], 0
        while router.queue or engine.n_active:
            for at, ev in events:
                if at == tick:
                    active = engine.n_active
                    router.apply(ev)
                    print(f"serve: tick {tick} {type(ev).__name__} with "
                          f"{active} in flight -> TP {engine.tp}, capacity "
                          f"{engine.capacity}, reshard moved "
                          f"{engine.last_reshard.get('bytes_moved', 0)} B")
            router.step()
            tick += 1
            check(tick < 20 * (max_new + 1) * requests,
                  "serve: requests did not finish")
        return router, tick

    t0 = time.perf_counter()
    faulty, ticks = run([(fail_tick, FailureEvent(domain=0)),
                         (repair_tick, RecoveryEvent(domain=0))])
    t_faulty = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref, ref_ticks = run([])
    t_ref = time.perf_counter() - t0
    got = {r.rid: list(r.generated) for r in faulty.completed}
    want = {r.rid: list(r.generated) for r in ref.completed}
    check(len(want) == requests and set(got) == set(want),
          f"serve: completed {sorted(got)} vs {sorted(want)}")
    for rid in want:
        first = next((i for i, (a, b) in enumerate(zip(got[rid], want[rid]))
                      if a != b), min(len(got[rid]), len(want[rid])))
        check(len(want[rid]) == max_new and got[rid] == want[rid],
              f"serve: request {rid} diverged through fail/repair at "
              f"generated token {first}:\n  faulty {got[rid]}\n"
              f"  ref    {want[rid]}")
    g = faulty.goodput()
    print(f"serve: {requests} requests x {max_new} tokens at default matmul "
          f"precision, streams equal "
          f"through fail->repair ({ticks} ticks, {g['preemptions']} "
          f"preemptions) and with no events ({ref_ticks} ticks); smoke "
          f"wall {t_faulty:.2f} s / {t_ref:.2f} s (include compile)")


def kernels(cfg, *, seq: int, batch: int, seed: int):
    """Each Pallas kernel through ``kernels.ops`` at this config's widths
    (ssd_scan at mamba2-780m's), against its jnp reference computed at
    highest matmul precision. Every call must dispatch compiled."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import telemetry
    from repro.configs import get_arch
    from repro.kernels import bucket, ops, ref
    from repro.reshard import planner

    rng = np.random.default_rng(seed)

    def normal(shape, dtype=jnp.float32, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    def maxerr(got, want):
        got, want = jax.tree.leaves(got), jax.tree.leaves(want)
        return max(float(np.max(np.abs(np.asarray(a, np.float32)
                                       - np.asarray(b, np.float32))))
                   for a, b in zip(got, want))

    s, hd = seq, cfg.head_dim
    q = normal((1, cfg.n_heads, s, hd), jnp.bfloat16)
    k = normal((1, cfg.n_kv_heads, s, hd), jnp.bfloat16)
    v = normal((1, cfg.n_kv_heads, s, hd), jnp.bfloat16)
    x = normal((batch * s, cfg.d_model))
    w = normal((cfg.d_model,), scale=0.1)

    m2 = get_arch("mamba2-780m")
    nh = m2.ssm.expand * m2.d_model // m2.ssm.head_dim
    bh, hp, ds = 2 * nh, m2.ssm.head_dim, m2.ssm.d_state
    sx = normal((bh, s, hp))
    sdt = jnp.asarray(rng.uniform(0.01, 0.2, size=(bh, s)), jnp.float32)
    sa = jnp.asarray(-rng.uniform(0.5, 2.0, size=(bh,)), jnp.float32)
    sb, sc = normal((bh, s, ds), scale=0.3), normal((bh, s, ds), scale=0.3)

    # one rank's send buckets for a granite MLP weight (d_model x 128-row
    # units) going from TP4 to TP3
    k_ff = cfg.d_ff // 128
    tables = planner.tables(planner.sync_key(k_ff, 4, 4),
                            planner.sync_key(k_ff, 4, 3), k_ff)
    src = jnp.concatenate([normal((tables.buf, cfg.d_model * 128)),
                           jnp.zeros((1, cfg.d_model * 128), jnp.float32)])
    send_idx = jnp.asarray(tables.send_idx[0], jnp.int32)
    leaves = [normal((148, cfg.d_model * 128)) for _ in range(2)]
    widths = tuple(l.shape[1] for l in leaves)

    cases = [
        ("flash_attention", 2e-2,
         lambda: ops.flash_attention(q, k, v, kind="causal"),
         lambda: ref.flash_attention_ref(q, k, v, kind="causal"),
         f"q {tuple(q.shape)} kv {tuple(k.shape)} bf16"),
        ("rmsnorm", 3e-5, lambda: ops.rmsnorm(x, w),
         lambda: ref.rmsnorm_ref(x, w), f"x {tuple(x.shape)} f32"),
        ("ssd_scan", 5e-4, lambda: ops.ssd_scan(sx, sdt, sa, sb, sc),
         lambda: ref.ssd_scan_ref(sx, sdt, sa, sb, sc),
         f"x {tuple(sx.shape)} B/C {tuple(sb.shape)} f32"),
        ("reshard_pack", 0.0, lambda: ops.reshard_pack(src, send_idx),
         lambda: ref.reshard_pack_ref(src, send_idx),
         f"src {tuple(src.shape)} idx {tuple(send_idx.shape)}"),
        ("bucket_pack", 0.0, lambda: ops.bucket_pack(leaves),
         lambda: bucket.bucket_pack_ref(leaves),
         f"2 leaves {tuple(leaves[0].shape)}"),
        ("bucket_unpack", 0.0,
         lambda: ops.bucket_unpack(bucket.bucket_pack_ref(leaves), widths),
         lambda: tuple(leaves), f"flat (148, {sum(widths)})"),
    ]
    rec = telemetry.Recorder(sinks=[telemetry.MemorySink()])
    for name, tol, kernel, reference, shapes in cases:
        with telemetry.recording(rec):
            got = jax.block_until_ready(kernel())
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(reference())
        err = maxerr(got, want)
        compiled = rec.total("kernels.dispatch", kernel=name, mode="compiled")
        interp = rec.total("kernels.dispatch", kernel=name, mode="interpret")
        print(f"kernels: {name} [{shapes}] dispatch compiled={compiled:g} "
              f"interpret={interp:g}  max|err| {err:.3e} (tol {tol:g})")
        check(compiled == 1 and interp == 0,
              f"kernels: {name} did not dispatch compiled exactly once")
        check(err <= tol, f"kernels: {name} max|err| {err:.3e} > {tol:g}")


def ntp_lifecycle(ncfg, mesh, *, seq: int, local_batch: int, seed: int,
                  fail_step: int = 2, repair_step: int = 4, steps: int = 6):
    """healthy -> FailureEvent(replica=1) -> (1, 2) -> RecoveryEvent ->
    (2, 2) under TraceRunner(verify=True): the NTP session co-trained with
    the dense single-copy reference (SGD). Run once at default matmul
    precision to print the gap, then asserted at highest precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ntp_train as nt
    from repro.optim import sgd
    from repro.runtime import (
        FailureEvent, NTPSession, RecoveryEvent, ScheduledEvent, TraceRunner,
    )

    d = mesh.shape["data"]

    def run(atol):
        session = NTPSession.create(ncfg, mesh, local_batch=local_batch,
                                    optimizer=sgd(0.05),
                                    key=jax.random.PRNGKey(seed))
        schedule = [
            ScheduledEvent(fail_step, FailureEvent(step=fail_step,
                                                   replica=1)),
            ScheduledEvent(repair_step, RecoveryEvent(step=repair_step,
                                                      replica=0)),
        ]

        def on_event(ev, plan):
            print(f"ntp4: step {ev.step} {type(ev).__name__} -> plan "
                  f"{plan.replica_tp} local_batches "
                  f"{[int(b) for b in session.local_batches]}",
                  flush=True)

        runner = TraceRunner(session, schedule, verify=True, atol=atol,
                             on_event=on_event)
        rng = np.random.default_rng(seed)
        hist = runner.run(lambda i: jnp.asarray(rng.integers(
            0, ncfg.vocab, (d * local_batch, seq + 1)), jnp.int32), steps)
        loss_gap = max(abs(h["loss"] - h["ref_loss"]) for h in hist)
        param_gap = max(t["canonical_err"] for t in runner.transitions)
        return session, runner, hist, loss_gap, param_gap

    print(f"ntp4: mesh data={d} model={mesh.shape['model']} on "
          f"{len(mesh.devices.flat)} devices, batch {d * local_batch} x seq "
          f"{seq}, SGD, events at steps {fail_step} (fail) and "
          f"{repair_step} (repair)")
    inf = float("inf")
    _, _, hist, loss_gap, param_gap = run(inf)
    print(f"ntp4: default matmul precision (not asserted): max |loss - ref| "
          f"{loss_gap:.3e}, max canonical param gap {param_gap:.3e}",
          flush=True)
    with jax.default_matmul_precision("highest"):
        session, runner, hist, loss_gap, param_gap = run(LOSS_ATOL)
    tps = [h["replica_tp"] for h in hist]
    print(f"ntp4: highest precision: losses "
          f"{[round(h['loss'], 5) for h in hist]}")
    print(f"ntp4: highest precision: max |loss - ref| {loss_gap:.3e}, max "
          f"canonical param gap {param_gap:.3e} (atol {LOSS_ATOL:g})")
    check(tps[0] == (2, 2) and tps[fail_step] == (1, 2)
          and tps[-1] == (2, 2), f"ntp4: plan sequence {tps}")
    # every sharded (unit-buffer) leaf must hold distinct shards on every
    # device of the mesh — not one device holding everything
    n_dev = len(mesh.devices.flat)
    for path, leaf in jax.tree_util.tree_flatten_with_path(session.params)[0]:
        if path[-1].key not in nt.UNIT_KEYS:
            continue
        devs = {sh.device for sh in leaf.addressable_shards}
        shard = leaf.addressable_shards[0].data.shape
        check(len(devs) == n_dev and math.prod(shard) * n_dev
              == math.prod(leaf.shape),
              f"ntp4: {jax.tree_util.keystr(path)} {leaf.shape} has shards "
              f"{shard} on {len(devs)} devices")
    print(f"ntp4: every unit-buffer leaf sharded over {n_dev} distinct "
          f"devices (e.g. layers[0].A {session.params['layers'][0]['A'].shape}"
          f" -> shards "
          f"{session.params['layers'][0]['A'].addressable_shards[0].data.shape})")


# ----------------------------------------------------------------------- main

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every one-chip phase; 4: the NTP fail/repair "
                         "lifecycle on a 2x2 mesh, and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {platform!r} "
              f"({len(devices)} device(s))", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} TPU device(s)", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    kind = devices[0].device_kind
    print(f"device: {platform} {kind} x{len(devices)}; compile cache {cache}")
    meter = CompileMeter()
    full, cfg = granite()
    print(f"config: {ARCH} at published widths (d_model {cfg.d_model}, "
          f"{cfg.n_heads}H/{cfg.n_kv_heads}KV, head_dim {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}, tied embeddings "
          f"{cfg.tie_embeddings}); cut: n_layers {full.n_layers} -> "
          f"{cfg.n_layers}; vocab kept at {cfg.vocab_size} (no layout here "
          f"needs it divisible)")

    if args.chips == 4:
        with meter.phase("ntp4 (2x2 mesh, fail -> repair vs dense reference)"):
            print(f"ntp4: NTPModelConfig at the same widths; cut: n_layers "
                  f"{NTP4_LAYERS}, seq {NTP_SEQ}")
            ntp_lifecycle(ntp_config(granite(NTP4_LAYERS)[1]),
                          jax.make_mesh((2, 2), ("data", "model")),
                          seq=NTP_SEQ, local_batch=NTP_LOCAL_BATCH,
                          seed=args.seed)
    else:
        with meter.phase("train/arch"):
            train_arch(cfg, seq=TRAIN_SEQ, batch=TRAIN_BATCH,
                       steps=TRAIN_STEPS, seed=args.seed)
        with meter.phase("train/ntp (1x1 mesh)"):
            print(f"train/ntp: NTPModelConfig at the same widths and depth; "
                  f"cut: seq {NTP_SEQ} (S x S attention scores)")
            train_ntp(ntp_config(cfg), jax.make_mesh((1, 1), ("data", "model")),
                      seq=NTP_SEQ, local_batch=NTP_LOCAL_BATCH,
                      steps=TRAIN_STEPS, seed=args.seed)
        with meter.phase("serve (fail -> repair mid-decode)"):
            serve_failover(cfg, requests=SERVE_REQUESTS,
                           prompt_len=SERVE_PROMPT, max_new=SERVE_NEW,
                           fail_tick=SERVE_NEW // 4,
                           repair_tick=SERVE_NEW // 2, seed=args.seed)
        with meter.phase("kernels"):
            kernels(cfg, seq=TRAIN_SEQ, batch=TRAIN_BATCH, seed=args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
