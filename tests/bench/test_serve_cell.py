"""The serving cell's driver, rehearsed at a tiny size on the CPU through
the benchmark's own functions: a sound run is correct, and a run with the
timed path broken underneath is not (one test per fault the cell can
have); its seeded arrivals, its latency arithmetic on hand-made stamps,
its check's sample, and its metric readers. The look for a chip is
skipped; nothing else is."""
from __future__ import annotations

import math
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import perfbench_tiny as T  # noqa: E402
from bench import harness  # noqa: E402

CELL = "granite-serve-chat"
SERVE_READERS = ("serve_mfu", "decode_slots_roofline", "prefill_ms.serve",
                 "queue_wait_ms.serve", "generator_lag_ms.serve",
                 "idle_share.serve", "ttft_p50_ms.serve", "itl_p99_ms.serve")
TRAIN_READERS = ("attention_ms.train", "mlp_ms.train", "loss_head_ms.train",
                 "optimizer_ms.train", "scoped_share.train")


def _driver():
    return T.tiny_cell(CELL).driver


def _correct(out) -> bool:
    return bool(out.checks) and all(c.ok for c in out.checks)


@pytest.fixture(scope="module")
def sound():
    return T.tiny_run(CELL, readings=True)


def test_serve_cell_is_correct_and_its_control_is_not(sound):
    run, out = sound
    assert _correct(out), [(c.name, c.value) for c in out.checks]
    line = harness.result_line(run, out, None)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "serve_itl_p90_ms"}
    assert line["checks"]["served_logit_gap_mean"]["limit"] == \
        T.TINY_LIMITS["served_logit_gap_mean"]
    w = out.records["window"]
    # attempted: the requests of the seed's schedule due in the window
    tr = run.cell.traffic
    plan = _driver().schedule(tr, run.seed, tr["warmup_s"] + run.seconds,
                              run.cell.sizes["vocab_size"])
    assert out.attempted == sum(p.due >= tr["warmup_s"] for p in plan) > 0
    assert len(w["ttft_s"]) == out.attempted
    assert out.failed == 0 and w["rejected"] == 0
    assert w["compiles"] == 0
    assert out.records["check"]["served_tokens"] >= 30
    limit = T.TINY_LIMITS["served_logit_gap_mean"]
    for name, nums in out.records["readings"].items():
        over = nums["served_logit_gap_mean"] > limit
        assert over == name.startswith("control"), (name, nums)


def _planted(monkeypatch, broken):
    from repro.models.transformer import Model

    orig = Model.decode_slots
    monkeypatch.setattr(Model, "decode_slots",
                        lambda self, *a: broken(*orig(self, *a), *a))


def test_serve_fault_token_altered(monkeypatch):
    import jax.numpy as jnp

    # every decoded token one id past the program's best
    _planted(monkeypatch, lambda logits, cache, *a: (
        jnp.roll(logits, 1, axis=-1), cache))
    _, out = T.tiny_run(CELL)
    assert not _correct(out)


def test_serve_fault_state_unchanged(monkeypatch):
    # the decode hands back the cache it was given: no new K and V kept
    _planted(monkeypatch, lambda logits, new_cache, params, cache, *a: (
        logits, cache))
    _, out = T.tiny_run(CELL)
    assert not _correct(out)


def test_serve_fault_rope_offset():
    with _driver().FAULTS["rope_offset"]():
        _, out = T.tiny_run(CELL)
    assert not _correct(out)


# ------------------------------------------------------------- arrivals

def test_schedule_repeats_for_a_seed_and_keeps_its_clips():
    D = _driver()
    tr = harness.load_cell(CELL).traffic
    vocab = 49155
    seconds = 60.0
    a = D.schedule(tr, 2**40 + 3, seconds, vocab)
    b = D.schedule(tr, 2**40 + 3, seconds, vocab)
    c = D.schedule(tr, 2**40 + 4, seconds, vocab)
    key = lambda s: [(p.due, p.max_new, p.prompt.tobytes()) for p in s]
    assert key(a) == key(b) and key(a) != key(c)
    for s in (a, c):
        assert [p.rid for p in s] == list(range(len(s)))
        for p in s:
            assert tr["prompt"]["min"] <= len(p.prompt) <= tr["prompt"]["max"]
            assert tr["output"]["min"] <= p.max_new <= tr["output"]["max"]
            assert p.prompt.min() >= 1 and p.prompt.max() < vocab
            assert len(p.prompt) + p.max_new <= tr["max_len"]
        dues = [p.due for p in s]
        assert dues == sorted(dues) and 0.0 < dues[0] and dues[-1] < seconds


def test_schedule_is_a_poisson_process():
    D = _driver()
    tr = harness.load_cell(CELL).traffic
    rate, seconds = tr["rate"], 4000.0
    plan = D.schedule(tr, 2**33 + 5, seconds, 64)
    n = len(plan)
    assert abs(n - rate * seconds) < 4 * math.sqrt(rate * seconds)
    gaps = np.diff([0.0] + [p.due for p in plan])
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.05)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.08)
    # the count in 5 s blocks varies as a Poisson count does
    counts = np.bincount([int(p.due // 5.0) for p in plan],
                         minlength=int(seconds // 5.0))
    assert counts.var() / counts.mean() == pytest.approx(1.0, abs=0.2)
    # lengths are drawn apart from the gaps: no correlation
    assert abs(np.corrcoef(gaps, [p.max_new for p in plan])[0, 1]) < 0.1


@pytest.mark.parametrize("part", ["prompt", "output"])
def test_lengths_follow_their_clipped_lognormals(part):
    D = _driver()
    spec = harness.load_cell(CELL).traffic[part]
    x = D.lengths(np.random.default_rng(7), spec, 20000)
    assert x.min() >= spec["min"] and x.max() <= spec["max"]
    assert np.median(x) == pytest.approx(spec["median"], rel=0.03)
    z = math.log(spec["max"] / spec["median"]) / spec["sigma"]
    above = 1 - 0.5 * (1 + math.erf(z / math.sqrt(2)))
    assert (x == spec["max"]).mean() == pytest.approx(above, abs=0.01)


# ------------------------------------------------------------- timings

def test_latencies_on_hand_made_stamps():
    D = _driver()
    T0, T1 = 10.0, 20.0
    reqs = [
        # due before the window: its gaps count where they end inside
        D.Timed(due=5.0, submit=5.1, stamps=[9.0, 9.5, 10.5, 11.0]),
        # due in the window, submitted late: timed from due, not submit
        D.Timed(due=12.0, submit=12.4, stamps=[12.6, 12.7, 19.9]),
        # due in the window, first token drained after it closed
        D.Timed(due=19.0, submit=19.05, stamps=[20.5, 20.6]),
        # due in the window, refused
        D.Timed(due=15.0, submit=15.0, accepted=False),
        # due after the window: not counted
        D.Timed(due=20.0, submit=20.0, stamps=[20.1, 20.2]),
    ]
    lat = D.latencies(reqs, T0, T1)
    assert lat["itl_s"] == pytest.approx([1.0, 0.5, 0.1, 7.2])
    assert lat["ttft_s"][:2] == pytest.approx([0.6, 1.5])
    assert math.isinf(lat["ttft_s"][2]) and len(lat["ttft_s"]) == 3
    assert D.end_to_end(lat) == {"serve_itl_p90_ms": pytest.approx(
        1e3 * np.percentile([1.0, 0.5, 0.1, 7.2], 90))}
    ttft = _reader("ttft_p50_ms.serve").read
    assert ttft(None, {"window": {"ttft_s": lat["ttft_s"]}}, None) == \
        pytest.approx(1500.0)
    # a median that is a refusal is no latency
    assert ttft(None, {"window": {"ttft_s": [math.inf, math.inf, 1.0]}},
                None) is None


def test_check_sample_holds_the_longest_and_follows_the_seed():
    D = _driver()
    p = np.arange(4, dtype=np.int32)
    finished = {r: (10.0 + r, p, np.arange(r % 7 + 1, dtype=np.int32))
                for r in range(12)}
    finished[99] = (5.0, p, np.arange(50, dtype=np.int32))   # before
    a = D.check_sample(finished, 10.0, 20.0, 4, 7)
    assert a == D.check_sample(finished, 10.0, 20.0, 4, 7)
    assert len(a) == 4 and 99 not in a and a[0] == 6
    draws = {tuple(D.check_sample(finished, 10.0, 20.0, 4, s))
             for s in range(8)}
    assert len(draws) > 1
    assert D.check_sample(finished, 30.0, 40.0, 4, 7) == []


# ------------------------------------------------------------- readers

def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def _stub(cell=CELL):
    return SimpleNamespace(cell=T.tiny_cell(cell),
                           devices=[SimpleNamespace(device_kind="TPU v5 lite")])


EMPTY_WINDOW = {"window_s": 10.0, "ticks": 0, "tick_live_positions": [],
                "decode_positions": [], "prefill_lengths": [], "admit_s": [],
                "queue_wait_s": [], "generator_lag_s": [], "ttft_s": [],
                "itl_p99_ms": None}


@pytest.mark.parametrize("name", SERVE_READERS + TRAIN_READERS)
def test_each_new_reader_returns_none_where_nothing_was_recorded(name):
    read = _reader(name).read
    run = _stub()
    assert read(run, {}, None) is None
    assert read(run, {"window": EMPTY_WINDOW}, None) is None
    assert read(run, {"window": EMPTY_WINDOW},
                {"idle_share": None, "modules": {}, "module_calls": {},
                 "busy_s": 0.0, "scopes": {}, "steps": 0}) is None


def test_serve_readers_on_recorded_numbers():
    run = _stub()
    C, s = run.cell.config, run.cell.sizes
    w = dict(EMPTY_WINDOW, tick_live_positions=[100, 300],
             decode_positions=[20, 40], prefill_lengths=[10],
             admit_s=[0.01, 0.03, 0.02], queue_wait_s=[0.5, 0.1, 0.2],
             generator_lag_s=[0.002, 0.004], ttft_s=[0.3, 0.1, 0.2],
             itl_p99_ms=96.5)
    rec = {"window": w}
    summary = {"idle_share": 0.25,
               "modules": {"jit_decode_slots": 0.02, "jit_prefill": 1.0},
               "module_calls": {"jit_decode_slots": 2, "jit_prefill": 1}}
    assert _reader("prefill_ms.serve").read(run, rec, None) == \
        pytest.approx(20.0)
    assert _reader("queue_wait_ms.serve").read(run, rec, None) == \
        pytest.approx(200.0)
    assert _reader("generator_lag_ms.serve").read(run, rec, None) == \
        pytest.approx(3.0)
    assert _reader("ttft_p50_ms.serve").read(run, rec, None) == \
        pytest.approx(200.0)
    assert _reader("itl_p99_ms.serve").read(run, rec, None) == 96.5
    assert _reader("idle_share.serve").read(run, rec, summary) == \
        pytest.approx(25.0)
    need = C.decode_tick_bytes(s, 200)
    assert _reader("decode_slots_roofline").read(run, rec, summary) == \
        pytest.approx(100 * need / (0.01 * 819e9))
    flops = (C.prefill_flops(s, 10) + C.serve_token_flops(s, 20, True)
             + C.serve_token_flops(s, 40, True))
    assert _reader("serve_mfu").read(run, rec, None) == \
        pytest.approx(100 * flops / (10.0 * 197e12))


def test_train_scope_readers_read_the_summary():
    run = _stub("granite-train")
    scopes = {"attention": {"forward": 0.1, "backward": 0.2, "other": 0.0},
              "mlp": {"forward": 0.05, "backward": 0.1, "other": 0.0},
              "loss_head": {"forward": 0.01, "backward": 0.02, "other": 0.0},
              "optimizer": {"forward": 0.0, "backward": 0.0, "other": 0.03},
              "unscoped": {"forward": 0.0, "backward": 0.0, "other": 0.09}}
    summary = {"scopes": scopes, "steps": 3, "busy_s": 0.6}
    got = {n: _reader(n).read(run, {}, summary) for n in TRAIN_READERS}
    assert got == pytest.approx({
        "attention_ms.train": 100.0, "mlp_ms.train": 50.0,
        "loss_head_ms.train": 10.0, "optimizer_ms.train": 10.0,
        "scoped_share.train": 85.0})


def test_decode_bytes_count_every_weight_once():
    import jax

    s = T.tiny_cell(CELL).sizes
    C = T.tiny_cell(CELL).config
    w = jax.eval_shape(lambda k: C.init(s, k), jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(w))
    assert C.decode_tick_bytes(s, 0) == 4 * n
    kv = s["num_hidden_layers"] * 2 * s["num_key_value_heads"] * s["head_dim"]
    assert C.decode_tick_bytes(s, 10) - C.decode_tick_bytes(s, 0) == \
        4 * 10 * kv
