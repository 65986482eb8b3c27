"""The NTP fail/repair cell's driver, rehearsed at a tiny size on four
virtual CPU devices through the benchmark's own functions (in a process of
its own, started by ``run_dist``, since the device count is fixed when JAX
starts): a sound run is
correct, and a run with the timed path broken underneath is not (one test
per fault the cell can have); the window's events fall in order; and the
cell's metric readers, on hand-made records."""
from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import ntp_tiny as T  # noqa: E402
from bench import harness  # noqa: E402

READERS = ("transition_s.ntp", "transition_bytes.ntp",
           "compiles_in_window.ntp", "ntp_degraded_share", "mfu.ntp",
           "idle_share.ntp")


@pytest.fixture(scope="module")
def runs(run_dist):
    out = run_dist("../bench/ntp_tiny.py", devices=4)
    return json.loads(out.strip().splitlines()[-1])


def _driver():
    return T.tiny_cell().driver


def test_ntp_cell_is_correct_and_its_control_is_not(runs):
    s = runs["sound"]
    assert s["correct"] is True, s["checks"]
    assert set(s["checks"]) == set(T.TINY_LIMITS)
    assert s["end_to_end"]["ntp_goodput_tokens_per_s"] > 0
    assert s["end_to_end"]["setup_s"] > 0 and s["attempted"] >= 4
    control = s["readings"]["control_bf16"]
    assert any(v > T.TINY_LIMITS[k] for k, v in control.items()), control


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange"])
def test_ntp_fault_fails_the_check(runs, fault):
    assert set(_driver().FAULTS) == {"state_unchanged", "half_batch",
                                     "no_exchange"}
    f = runs["faults"][fault]
    assert f["correct"] is False, f["checks"]


def test_window_events_fall_in_order(runs):
    s = runs["sound"]
    tr, seconds = T.tiny_cell().traffic, s["seconds"]
    fail, repair = s["transitions"]
    assert (fail["kind"], repair["kind"]) == ("FailureEvent",
                                              "RecoveryEvent")
    assert tr["fail_at"] * seconds <= fail["t_event"] < fail["t_applied"] \
        <= fail["t_first_step"] <= repair["t_event"]
    # the window stays open until the repair's first step has run
    assert tr["repair_at"] * seconds <= repair["t_event"]
    assert repair["t_first_step"] <= s["window_s"]
    assert (fail["plan"], repair["plan"]) == ([1, 2], [2, 2])
    distinct = [p for k, p in enumerate(s["plans"])
                if k == 0 or p != s["plans"][k - 1]]
    assert distinct == [[2, 2], [1, 2], [2, 2]]
    assert s["check_plans"] == [[2, 2]] * 2 + [[1, 2]] * 2 + [[2, 2]] * 2
    for t in (fail, repair):
        assert t["bytes_moved"] > 0
        assert list(t["marks"]) == ["planned", "gathered", "repacked",
                                    "placed", "executed"]


def test_usable_rows_follow_the_surviving_tp():
    D = _driver()
    assert D.usable_rows((2, 2), 2, 8) == [8, 8]
    assert D.usable_rows((1, 2), 2, 8) == [4, 8]
    w = D.row_weight((1, 2), 2, 4)
    assert w.tolist() == [1, 1, 0, 0, 1, 1, 1, 1]
    tr = T.tiny_cell().traffic
    assert D.expected_plans(tr) == [(2, 2), (1, 2), (2, 2)]


# ------------------------------------------------------------- readers

def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def _stub():
    return SimpleNamespace(cell=T.tiny_cell(),
                           devices=[SimpleNamespace(device_kind="TPU v5 lite")]
                           * 4)


def _window():
    """Four healthy steps in 2 s, the failure at 2 s and its first step
    done at 5 s, three degraded steps to 8 s, the repair at 8 s and its
    first step done at 10 s, two healthy steps to 11 s."""
    h, g = ((2, 2), 1000, False), ((1, 2), 750, False)
    steps = [h] * 4 + [((1, 2), 750, True)] + [g] * 3 \
        + [((2, 2), 1000, True)] + [h] * 2
    trans = [{"t_event": 2.0, "t_applied": 4.0, "t_first_step": 5.0,
              "bytes_moved": 300},
             {"t_event": 8.0, "t_applied": 9.5, "t_first_step": 10.0,
              "bytes_moved": 200}]
    return {"steps": len(steps), "tokens": sum(t for _, t, _ in steps),
            "window_s": 11.0, "steps_run": steps, "transitions": trans,
            "programs": 2, "compiles": 0}


def test_ntp_readers_on_recorded_numbers():
    run = _stub()
    rec = {"window": _window()}
    got = {n: _reader(n).read(run, rec, {"idle_share": 0.4}) for n in READERS}
    flops = run.cell.config.train_flops_per_token(run.cell.sizes,
                                                  run.cell.traffic["seq"])
    assert got == pytest.approx({
        "transition_s.ntp": 3.0 + 2.0,
        "transition_bytes.ntp": 500,
        "compiles_in_window.ntp": 0,
        # healthy 4000 tokens in 2 s; degraded 2250 in 8 - 5 = 3 s
        "ntp_degraded_share": 100 * (2250 / 3.0) / (4000 / 2.0),
        "mfu.ntp": 100 * 10000 / 11.0 * flops / (4 * 197e12),
        "idle_share.ntp": 40.0})


@pytest.mark.parametrize("name", READERS)
def test_each_ntp_reader_returns_none_where_nothing_was_recorded(name):
    read = _reader(name).read
    run = _stub()
    assert read(run, {"window": None}, None) is None
    assert read(run, {}, None) is None


def test_degraded_share_needs_degraded_steps():
    w = _window()
    w["steps_run"] = [s for s in w["steps_run"] if s[0] != (1, 2) or s[2]]
    assert _reader("ntp_degraded_share").read(_stub(), {"window": w},
                                              None) is None


def test_flops_count_every_matmul_parameter_once():
    import jax

    cell = T.tiny_cell()
    C, s = cell.config, cell.sizes
    w = jax.eval_shape(lambda k: C.init(s, k), jax.random.PRNGKey(0))
    matmul = sum(np.prod(x.shape) for k, x in
                 [(k, v) for L in w["layers"] for k, v in L.items()]
                 if not k.startswith("ln")) + np.prod(w["head"].shape)
    seq = cell.traffic["seq"]
    attn = 6 * s["num_hidden_layers"] * seq * s["num_attention_heads"] \
        * s["head_dim"]
    assert C.train_flops_per_token(s, seq) == 6 * matmul + attn


def test_config_is_the_prototype_block():
    import dataclasses

    cell = harness.make_cell(
        T.CELL, 4, harness.BENCH / "configs" / f"{T.CONFIG}.json", T.TRAFFIC)
    cfg = cell.config.model_config(cell.sizes)
    assert dataclasses.asdict(cfg) == dict(
        dataclasses.asdict(cfg), d_model=2048, n_kv_groups=8, q_per_kv=4,
        head_dim=64, d_ff=8192, unit_rows=128, n_layers=4, vocab=49155)
    bad = dict(cell.sizes, departures=dict(
        cell.sizes["departures"], rms_norm_eps={"source": 1e-5, "run": 1e-5}))
    with pytest.raises(ValueError):
        cell.config.model_config(bad)
