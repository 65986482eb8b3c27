"""``bench/trace_scopes.py``: device time by named scope and pass, and idle
time by the program's host spans, on two traces recorded on a TPU v5e.

``tpu_v5e_scopes.xplane.pb`` (``record_scopes_trace.py``): a tiny jitted
step with ``attention`` and ``mlp`` scopes under ``value_and_grad`` and its
norm-clipped update under ``optimizer``, four times under ``bench.window``, each input
made in a ``data.batch`` span that sleeps 2 ms, each step inside a
``train`` step marker and a ``session.step`` span.
``tpu_v5e_small.xplane.pb``: the trace ``test_perfbench_trace.py`` reads,
which has no scopes and no program spans."""
from __future__ import annotations

import struct
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402
from bench import trace_scopes as ts  # noqa: E402

SCOPED = HERE / "tpu_v5e_scopes.xplane.pb"
SMALL = HERE / "tpu_v5e_small.xplane.pb"


@pytest.fixture(scope="module")
def scoped():
    return ts.reduce_all(SCOPED)


def test_scopes_split_by_scope_and_pass(scoped):
    scopes = scoped["scopes"]
    assert set(scopes) <= set(ts.SCOPES) | {ts.UNSCOPED}
    for name in ("attention", "mlp"):
        assert scopes[name]["forward"] > 0, name
        assert scopes[name]["backward"] > 0, name
        assert scopes[name]["other"] == 0, name
    assert scopes["optimizer"]["other"] > 0
    assert scopes["optimizer"]["forward"] == scopes["optimizer"]["backward"] \
        == 0
    # every op counted once: together they fill the busy time
    total = sum(sum(p.values()) for p in scopes.values())
    assert total == pytest.approx(scoped["busy_s"], rel=0.02)
    assert scoped["layers"]["scoped_share"] > 50


def test_idle_gaps_name_the_program_spans(scoped):
    gaps = dict(scoped["idle_gaps_program"])
    # four 2 ms sleeps inside data.batch, innermost within bench.batch
    assert gaps["data.batch"] > 0.006
    assert "bench.batch" not in gaps or gaps["bench.batch"] < 1e-3
    assert sum(gaps.values()) == pytest.approx(
        scoped["window_s"] - scoped["busy_s"], rel=1e-3)


def test_step_markers_and_program_spans_in_the_window(scoped):
    assert scoped["steps"] == 4
    assert scoped["program_spans"]["data.batch"] == 4
    assert scoped["program_spans"]["session.step"] == 4
    per_step = scoped["layers"]
    assert set(per_step) == {"attention_ms", "mlp_ms", "loss_head_ms",
                             "optimizer_ms", "scoped_share"}
    assert per_step["loss_head_ms"] == 0
    assert sum(v for k, v in per_step.items() if k.endswith("_ms")) <= \
        1e3 * scoped["busy_s"] / scoped["steps"]


def test_reduce_keys_are_untouched():
    """``reduce`` holds ``summarize``'s keys, unchanged, beside its own, and
    ``reduce_all`` adds only ``layers``; on a trace with only the harness's
    spans the program's idle split is the harness's own."""
    base = tr.reduce(SMALL)
    added = ts.summarize(SMALL)
    own = {"window_s", "busy_s", "idle_share", "devices", "modules",
           "module_calls", "breakdown"}
    assert not own & set(added)
    assert set(base) == own | set(added)
    assert {k: base[k] for k in added} == added
    full = ts.reduce_all(SMALL)
    assert {k: full[k] for k in base} == base
    assert set(full) - set(base) == {"layers"}
    assert full["scopes"] == {ts.UNSCOPED: pytest.approx(
        {"forward": 0.0, "backward": 0.0, "other": base["busy_s"]},
        rel=1e-3)}
    prog = dict(full["idle_gaps_program"])
    for name, s in base["breakdown"]["idle_gaps"]:
        assert prog[name] == pytest.approx(s, rel=1e-3, abs=1e-6)
    assert full["steps"] == 0


@pytest.mark.parametrize("op_name, want", [
    ("jit(train_step)/transpose(jvp(attention))/dot_general:",
     ("attention", "backward")),
    ("jit(step)/jvp()/while/body/closed_call/attention/tanh",
     ("attention", "forward")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general", ("mlp", "backward")),
    ("jit(step)/optimizer/mul", ("optimizer", "other")),
    ("jit(step)/jvp(loss_head)/norm/mul", ("loss_head", "forward")),
    ("jit(step)/jvp(attention_scores)/mul", ("unscoped", "forward")),
    ("", ("unscoped", "other")),
])
def test_scope_of(op_name, want):
    assert ts.scope_of(op_name) == want


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def test_wire_reader():
    msg = (_varint(1 << 3) + _varint(300)                   # varint
           + _varint(2 << 3 | 2) + _varint(3) + b"abc"      # bytes
           + _varint(3 << 3 | 1) + struct.pack("<q", -5)    # fixed64
           + _varint(4 << 3) + _varint((1 << 64) - 7))      # negative int64
    fields = [(f, bytes(v) if isinstance(v, memoryview) else v)
              for f, v in ts._fields(memoryview(msg))]
    assert fields == [(1, 300), (2, b"abc"), (3, -5), (4, (1 << 64) - 7)]
    assert ts._signed(fields[3][1]) == -7
