"""``bench/trace_reduce.py`` on a small trace recorded on a TPU v5e: a
jitted 1024 x 1024 matmul run four times under ``bench.window``, each with
``bench.step``, ``bench.wait`` and a 2 ms ``bench.batch`` sleep."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402

TRACE = HERE / "tpu_v5e_small.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return tr.reduce(TRACE)


def test_window_busy_and_idle(summary):
    assert summary["devices"] == 1
    assert 0.01 < summary["window_s"] < 0.02
    assert 0 < summary["busy_s"] < summary["window_s"]
    assert summary["idle_share"] == pytest.approx(
        1 - summary["busy_s"] / summary["window_s"])


def test_programs_and_ops_are_named_stably(summary):
    assert set(summary["modules"]) == {"jit__lambda"}
    # four runs of the program, the first ended before the window opened
    assert summary["module_calls"] == {"jit__lambda": pytest.approx(3.0)}
    ops = summary["breakdown"]["device_ops"]
    assert 1 <= len(ops) <= tr.TOP
    assert ops[0][0] == "jit__lambda:fusion f32[]"
    assert sum(s for _, s in ops) == pytest.approx(summary["busy_s"],
                                                   rel=1e-3)


def test_idle_gaps_are_attributed_to_host_spans(summary):
    gaps = dict(summary["breakdown"]["idle_gaps"])
    assert set(gaps) <= {"bench.step", "bench.wait", "bench.batch",
                         "host:unannotated"}
    # four 2 ms sleeps in bench.batch leave the device idle
    assert gaps["bench.batch"] > 0.006
    assert sum(gaps.values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"], rel=1e-6)


def test_merge_and_labels():
    assert tr._merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    name = ("%fusion.400 = f32[2,4096,8,64]{1,3,2,0:T(8,128)} fusion(bf16[2]"
            "{0:T(8,128)(2,1)S(1)} %bitcast.453), kind=kOutput")
    assert tr.op_label(name, "jit_step") == "jit_step:fusion f32[2,4096,8,64]"
    loop = ("%while.10 = (s32[]{:T(128)}, f32[2,4]{1,0}) while((s32[], "
            "f32[2,4]) %tuple.5), condition=%cond")
    assert tr.op_kind(loop)[0] == "while"
    assert tr.module_label("jit_prefill(1234)") == "jit_prefill"
