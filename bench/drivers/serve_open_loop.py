"""Open-loop chat serving through ``ServeSession.create(...)`` behind a
``Router`` (the ``launch/serve.py --full`` path): one replica, greedy
decoding, no failure events.

Traffic parameters: ``slots``, ``max_len``, ``prefill_len``, ``n1`` and
``policy`` build the session; ``rate`` (requests a second) sets the
arrivals, a Poisson process: independent exponential gaps of mean ``1 /
rate``, each request's prompt and output lengths independent draws of the
lognormals ``prompt`` and ``output`` (``median``, ``sigma``), rounded and
clipped to [``min``, ``max``], all drawn from the seed. ``warmup_s`` runs
the same stream before the window; ``check_requests`` is the size of the
sample the reference follows; ``drain_s`` bounds the wait for the window's
last first tokens.

Every time is the host's wall clock. A request is due at its scheduled
time and timed from then; the loop submits it at its first free moment
after that. Each token is stamped when the ``Router.step`` that emitted it
returns. After the window closes the loop keeps stepping, with no new
arrivals, until every request due in the window has its first token.

Correct: once the window has closed and the session is freed, the
reference runs, in float32 at the matmul precision the configuration
states, over the prompt and served tokens of a sample of the requests
finished in the window, drawn from the seed with the longest among them.
At each served position it reads the gap by which the served token's
logit lies below the reference's best; the number compared is the mean
of those gaps. The widest gap is recorded beside it: it is one near-tie's
rounding, and on the chip it did not separate the program from the
bfloat16 control, where the mean did (PERF.md).
"""
from __future__ import annotations

import contextlib
import gc
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Optional

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench import harness  # noqa: E402
from bench.harness import Check, Outcome, span  # noqa: E402

CHECK = "served_logit_gap_mean"


# ------------------------------------------------------------- the arrivals

def lengths(rng, spec: dict, n: int) -> np.ndarray:
    """``n`` draws of a lognormal (``median``, ``sigma``), rounded and
    clipped to [``min``, ``max``]."""
    x = np.rint(spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n)))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


@dataclass
class Planned:
    rid: int
    due: float              # seconds after the stream's start
    prompt: np.ndarray      # int32 token ids
    max_new: int


def schedule(tr: dict, seed: int, seconds: float, vocab: int
             ) -> List[Planned]:
    """The requests due in the first ``seconds`` of the stream."""
    rng = harness.rng(seed, 2)
    due, t = [], rng.exponential(1.0 / tr["rate"])
    while t < seconds:
        due.append(t)
        t += rng.exponential(1.0 / tr["rate"])
    plen = lengths(rng, tr["prompt"], len(due))
    olen = lengths(rng, tr["output"], len(due))
    return [Planned(i, float(d),
                    rng.integers(1, vocab, size=int(p)).astype(np.int32),
                    int(o))
            for i, (d, p, o) in enumerate(zip(due, plen, olen))]


# ------------------------------------------------------------- the timings

@dataclass
class Timed:
    """One request's host times (``time.perf_counter``)."""

    due: float
    submit: Optional[float] = None
    accepted: bool = True
    admit: Optional[float] = None
    stamps: List[float] = field(default_factory=list)


def latencies(reqs, t0: float, t1: float) -> dict:
    """The end-to-end numbers over the window [t0, t1): every gap between
    successive tokens of a request that ends in the window, and for each
    request due in the window the time from due to its first token (inf
    for one that never had it, or was refused)."""
    itl, ttft = [], []
    for r in reqs:
        s = r.stamps
        itl += [b - a for a, b in zip(s, s[1:]) if t0 <= b < t1]
        if t0 <= r.due < t1:
            ttft.append(s[0] - r.due if s and r.accepted else math.inf)
    return {"itl_s": itl, "ttft_s": ttft}


def end_to_end(lat: dict) -> dict:
    """The token gaps' 90th percentile. At 4/5 of the knee about 5% of the
    gaps end in a step that also admits (PERF.md): the 90th lies among the
    plain decode steps, where the 95th and 99th flip between modes."""
    if not lat["itl_s"]:
        return {}
    return {"serve_itl_p90_ms": 1e3 * float(np.percentile(lat["itl_s"], 90))}


# ------------------------------------------------------------- the session

def setup(run):
    import jax

    from repro.serve import Request, Router, ServeSession

    s, tr, C = run.cell.sizes, run.cell.traffic, run.cell.config
    cfg = C.arch_config(s)
    key = jax.random.PRNGKey(harness.seed32(run.seed, 1))
    vp = cfg.padded_vocab()
    params = jax.jit(lambda k: C.to_program(s, C.init(s, k), vp))(key)
    session = ServeSession.create(
        cfg, replicas=1, n1=tr["n1"], slots=tr["slots"],
        max_len=tr["max_len"], prefill_len=tr["prefill_len"],
        policy=tr["policy"], params=params)
    del params
    # every program the stream uses, compiled or loaded here: the prefill,
    # the slot decode and the eager ops around them
    router = Router(session)
    router.submit(Request(rid=-1, prompt=np.arange(1, 17, dtype=np.int32),
                          max_new=2))
    while router.queue or session.engines[0].n_active:
        router.step()
    return {"session": session, "router": Router(session)}


class Recorder:
    """Host times of the stream, taken around the engine's ``admit`` and
    ``tick`` (wrapped on the instance) and the router's ``submit`` and
    ``step``; each is also a ``bench.*`` span in the profiler's trace."""

    def __init__(self, engine):
        self.engine = engine
        self.reqs = {}
        self.admits = []    # (start, end, prompt length)
        self.ticks = []     # (start, end, live positions, the positions
        #                      attended by each decode whose token is served)
        self._admit, self._tick = engine.admit, engine.tick
        engine.admit, engine.tick = self.admit, self.tick

    def admit(self, req):
        t = time.perf_counter()
        with span("bench.admit"):
            ok = self._admit(req)
        self.reqs[req.rid].admit = t
        self.admits.append((t, time.perf_counter(), len(req.prompt)))
        return ok

    def tick(self):
        live, served = 0, []
        for r in self.engine.in_flight:
            pos = len(r.prompt) + len(r.generated) + 1
            live += pos
            if r.remaining > 1:   # the token this decode computes is served
                served.append(pos)
        t = time.perf_counter()
        with span("bench.tick"):
            done = self._tick()
        self.ticks.append((t, time.perf_counter(), live, served))
        return done

    def stamp(self, reqs, t):
        for r in reqs:
            rec = self.reqs[r.rid]
            while len(rec.stamps) < len(r.generated):
                rec.stamps.append(t)


def stream(st, plan, t_origin, t_end, *, drain_until=None, wait_for=()):
    """Drive the router from ``st["next"]`` in ``plan`` until ``t_end``:
    submit each request at its first free moment after it is due, step,
    stamp. With ``drain_until``, submit nothing more and step until every
    request in ``wait_for`` has its first token or that time has come."""
    from repro.serve import Request

    router, rec = st["router"], st["rec"]
    engine = rec.engine
    while True:
        now = time.perf_counter()
        if drain_until is None:
            # everything due by now, and before the end, is submitted
            while st["next"] < len(plan) and \
                    t_origin + plan[st["next"]].due <= now and \
                    t_origin + plan[st["next"]].due < t_end:
                p = plan[st["next"]]
                st["next"] += 1
                timed = Timed(t_origin + p.due)
                rec.reqs[p.rid] = timed
                with span("bench.submit"), st["pauses"].part("submit"):
                    timed.submit = time.perf_counter()
                    timed.accepted = router.submit(
                        Request(rid=p.rid, prompt=p.prompt, max_new=p.max_new))
            if now >= t_end:
                return
        elif now >= drain_until or all(rec.reqs[r].stamps for r in wait_for
                                       if rec.reqs[r].accepted):
            return
        if not router.queue and engine.n_active == 0:
            nxt = (t_origin + plan[st["next"]].due
                   if drain_until is None and st["next"] < len(plan)
                   else t_end)
            with span("bench.idle"):
                time.sleep(max(0.0, min(nxt, t_end) - now))
            if drain_until is not None:
                return
            continue
        with span("bench.step"), st["pauses"].part("step"):
            done = router.step()
        t = time.perf_counter()
        rec.stamp(engine.in_flight, t)
        rec.stamp(done, t)
        for r in done:
            st["finished"][r.rid] = (t, np.asarray(r.prompt, np.int32),
                                     np.asarray(r.generated, np.int32))


def window_records(rec, st, t0, t1, meter_at, lat):
    in_win = lambda t: t0 <= t < t1
    ticks = [x for x in rec.ticks if in_win(x[1])]
    admits = [x for x in rec.admits if in_win(x[1])]
    due = [r for r in rec.reqs.values() if in_win(r.due)]
    return {
        "t0": t0, "t1": t1, "window_s": t1 - t0,
        "ticks": len(ticks),
        "tick_live_positions": [x[2] for x in ticks],
        "decode_positions": [p for x in ticks for p in x[3]],
        "prefill_lengths": [x[2] for x in admits],
        "admit_s": [x[1] - x[0] for x in admits],
        "queue_wait_s": [r.admit - r.due for r in due if r.admit is not None],
        "generator_lag_s": [r.submit - r.due for r in due
                            if r.submit is not None],
        "ttft_s": lat["ttft_s"],
        "itl_mean_ms": 1e3 * float(np.mean(lat["itl_s"])) if lat["itl_s"]
        else None,
        "itl_p99_ms": 1e3 * float(np.percentile(lat["itl_s"], 99))
        if lat["itl_s"] else None,
        "queue_at_start": st["queue_at"][0], "queue_at_end": st["queue_at"][1],
        "rejected": sum(not r.accepted for r in due),
        "compiles": meter_at[1] - meter_at[0],
    }


# ------------------------------------------------------------- the check

def check_sample(finished: dict, t0: float, t1: float, k: int, seed: int):
    """rids of ``k`` requests finished in the window, drawn from the seed,
    with the longest (most served tokens, then longest prompt) among them."""
    rids = sorted(r for r, (t, _, _) in finished.items() if t0 <= t < t1)
    if not rids or k <= 0:
        return []
    longest = max(rids, key=lambda r: (len(finished[r][2]),
                                       len(finished[r][1]), -r))
    rest = [r for r in rids if r != longest]
    pick = harness.rng(seed, 3).permutation(len(rest))[: k - 1]
    return [longest] + sorted(rest[i] for i in pick)


def _scorer(run, dtype):
    """A jitted ``(w, tokens, next) -> (gap, top)`` over one padded row: at
    each position, how far the logit of ``next`` lies below the best, and
    which token is best, under the reference's forward in ``dtype``."""
    import jax
    import jax.numpy as jnp

    C, s = run.cell.config, run.cell.sizes

    @jax.jit
    def score(w, tokens, nxt):
        logits = C.forward(s, w, tokens, dtype)[0]
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, nxt[0][:, None], axis=-1)[:, 0]
        return best - got, jnp.argmax(logits, axis=-1)

    return score


def _rows(run, sample):
    """(tokens, next tokens, first served position, served count) of each
    sampled request, padded to ``max_len``."""
    L = run.cell.traffic["max_len"]
    out = []
    for prompt, gen in sample:
        seq = np.concatenate([prompt, gen]).astype(np.int32)
        tok = np.zeros((1, L), np.int32)
        nxt = np.zeros((1, L), np.int32)
        tok[0, : len(seq) - 1] = seq[:-1]
        nxt[0, : len(seq) - 1] = seq[1:]
        out.append((tok, nxt, len(prompt) - 1, len(gen)))
    return out


def reference_gaps(run, sample, precision: str, *, control=False):
    """The served tokens' gaps below the reference's best, per request,
    with the reference in float32 at ``precision``; with ``control``, the
    gaps of the tokens that the reference computed in bfloat16 throughout
    puts first at the same positions instead."""
    import jax
    import jax.numpy as jnp

    C, s = run.cell.config, run.cell.sizes
    key = jax.random.PRNGKey(harness.seed32(run.seed, 1))
    rows = _rows(run, sample)
    picks = [nxt for _, nxt, _, _ in rows]
    if control:
        w16 = jax.jit(lambda k: jax.tree.map(
            lambda x: x.astype(jnp.bfloat16), C.init(s, k)))(key)
        score16 = _scorer(run, jnp.bfloat16)
        with jax.default_matmul_precision(s["matmul_precision"]):
            picks = []
            for tok, nxt, first, n in rows:
                top = np.asarray(score16(w16, tok, nxt)[1])
                p = nxt.copy()
                p[0, first: first + n] = top[first: first + n]
                picks.append(p)
        del w16
    w = jax.jit(lambda k: C.init(s, k))(key)
    score = _scorer(run, jnp.float32)
    with jax.default_matmul_precision(precision):
        out = [np.asarray(score(w, tok, p)[0])[first: first + n]
               for (tok, _, first, n), p in zip(rows, picks)]
    del w
    return out


def gap_numbers(gaps) -> dict:
    """Over all served tokens of the sample: the mean of their gaps below
    the reference's best (the number compared), the widest gap, and the
    share of tokens that were not the reference's best."""
    allg = np.concatenate(gaps) if gaps else np.zeros(0)
    if not allg.size:
        return {CHECK: math.nan, "served_tokens": 0}
    return {CHECK: float(allg.mean()), "served_logit_gap": float(allg.max()),
            "flipped_share": float((allg > 0).mean()),
            "served_tokens": int(allg.size)}


def readings(run, sample):
    """The program against the reference at both precisions, and the
    bfloat16-throughout control against each."""
    out = {}
    for prec in ("highest", "default"):
        out[f"program@{prec}"] = gap_numbers(reference_gaps(run, sample, prec))
        out[f"control_bf16@{prec}"] = gap_numbers(
            reference_gaps(run, sample, prec, control=True))
    return out


@contextlib.contextmanager
def rope_offset():
    """A planted fault: each decoded token's query and key rotated one
    position too far (the prefill stays right)."""
    import repro.models.attention as A

    orig = A.apply_rope

    def shifted(x, positions, theta):
        return orig(x, positions + 1 if x.shape[1] == 1 else positions, theta)

    A.apply_rope = shifted
    try:
        yield
    finally:
        A.apply_rope = orig


FAULTS = {"rope_offset": rope_offset}


# ------------------------------------------------------------- a run

def run(run):
    lim = harness.load_limits(run)
    tr = run.cell.traffic
    st = setup(run)
    engine = st["session"].engines[0]
    st["rec"] = Recorder(engine)
    st.update(next=0, finished={}, queue_at=[None, None],
              pauses=harness.HostPauses())
    warm = tr["warmup_s"]
    plan = schedule(tr, run.seed, warm + run.seconds,
                    run.cell.sizes["vocab_size"])
    t_origin = time.perf_counter()
    t0, t1 = t_origin + warm, t_origin + warm + run.seconds
    stream(st, plan, t_origin, t0)
    st["pauses"].close()
    run.setup_done()
    meter_at = [run.meter.snapshot()[1] if run.meter else 0, 0]
    st["queue_at"][0] = len(st["router"].queue)
    with run.traced():
        st["pauses"] = harness.HostPauses()
        stream(st, plan, t_origin, t1)
    host = st["pauses"].close()
    st["queue_at"][1] = len(st["router"].queue)
    meter_at[1] = run.meter.snapshot()[1] if run.meter else 0
    due = [p.rid for p in plan if t_origin + p.due >= t0]
    stream(st, plan, t_origin, t1, drain_until=t1 + tr["drain_s"],
           wait_for=due)
    mem = harness.memory_peak_bytes(run.devices)
    rec = st["rec"]
    lat = latencies(rec.reqs.values(), t0, t1)
    win = window_records(rec, st, t0, t1, meter_at, lat)
    win["host"] = host
    attempted = len(lat["ttft_s"])
    failed = sum(not math.isfinite(x) for x in lat["ttft_s"])
    picked = check_sample(st["finished"], t0, t1, tr["check_requests"],
                          run.seed)
    sample = [st["finished"][r][1:] for r in picked]
    engine = rec = None
    st.clear()
    gc.collect()
    checks, records = [], {"window": win, "setup_compile_s":
                           run.setup_compile_s, "sample": picked}
    if sample:
        nums = gap_numbers(reference_gaps(
            run, sample, run.cell.sizes["matmul_precision"]))
        records["check"] = nums
        checks = [Check(CHECK, nums[CHECK], lim[CHECK])]
        if run.readings:
            records["readings"] = readings(run, sample)
    print(f"serve window: {attempted} due, {failed} failed, "
          f"{win['ticks']} ticks, {len(win['admit_s'])} admits, queue "
          f"{win['queue_at_start']} -> {win['queue_at_end']}, "
          f"{win['compiles']} compiles; TTFT median "
          f"{1e3 * float(np.median(lat['ttft_s'])) if lat['ttft_s'] else 0:.2f}"
          f" ms, ITL mean {win['itl_mean_ms'] or 0:.3f} ms, p99 "
          f"{win['itl_p99_ms'] or 0:.3f} ms; "
          + harness.HostPauses.describe(host), file=sys.stderr)
    e2e = {"setup_s": run.setup_s, **end_to_end(lat)}
    return Outcome(attempted=attempted, failed=failed, end_to_end=e2e,
                   records=records, checks=checks, memory_peak_bytes=mem)
