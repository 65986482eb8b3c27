"""ISSUE 8 acceptance: the telemetry spine recording a real fail -> boost ->
repair lifecycle on 8 fake CPU devices, then folded offline.

One NTP-PW trace (the session_lifecycle.py schedule) runs with a JSONL +
memory recorder active; the checks are the issue's acceptance bullets:

* the goodput-decomposition report reconstructed FROM THE STREAM matches
  the orchestrator's own `TraceRunner.goodput()` to < 0.1 % (it is equal
  by construction — same per-step sums, same mean);
* every executed ``session.transition`` span carries byte/message counts
  equal to the session's `last_transition` TransferStats ledger EXACTLY,
  and the Perfetto trace rows carry the same numbers;
* the Chrome-trace export is loadable JSON with one swimlane per
  subsystem;
* a second identical run with the recorder OFF produces bit-identical
  losses — the off path cannot perturb numerics.
"""
import json
import os
import tempfile

import numpy as np

import jax

from repro import telemetry
from repro.core.power import PowerModel
from repro.launch.telemetry_report import GOODPUT_KEYS, report
from repro.optim import sgd
from repro.runtime import (
    FailureEvent, NTPModelConfig, NTPSession, PowerPolicy, RecoveryEvent,
    ScheduledEvent, TraceRunner,
)
from repro.telemetry import (
    JsonlSink, MemorySink, Recorder, load_jsonl, write_chrome_trace,
)

LB, SEQ, STEPS = 4, 32, 15
cfg = NTPModelConfig(d_model=64, n_kv_groups=4, q_per_kv=2, head_dim=16,
                     d_ff=256, unit_rows=64, n_layers=2, vocab=128)
mesh = jax.make_mesh((2, 4), ("data", "model"))


def schedule():
    return [
        ScheduledEvent(3, FailureEvent(step=3, replica=0)),    # (4,4)->(3,4)
        ScheduledEvent(6, FailureEvent(step=6, domain=0)),     # ->(2,4)
        ScheduledEvent(9, RecoveryEvent(step=9, domain=0)),    # ->(3,4)
        ScheduledEvent(12, RecoveryEvent(step=12, replica=0)),  # ->(4,4)
    ]


def run_once(recorder, ledger=None):
    session = NTPSession.create(
        cfg, mesh, local_batch=LB, optimizer=sgd(0.05),
        key=jax.random.PRNGKey(0),
        power_policy=PowerPolicy(name="ntp_pw", model=PowerModel(max_boost=2.5)),
    )
    rng = np.random.default_rng(0)

    def batch(i):
        import jax.numpy as jnp
        return jnp.asarray(rng.integers(0, cfg.vocab, (2 * LB, SEQ + 1)))

    on_event = None
    if ledger is not None:
        def on_event(ev, plan):
            lt = session.last_transition
            ledger.append({"bytes_moved": lt.bytes_moved,
                           "messages": lt.messages})
    runner = TraceRunner(session, schedule(), on_event=on_event, drain_every=4)
    with telemetry.recording(recorder):
        hist = runner.run(batch, STEPS)
    return runner, hist


tmp = tempfile.mkdtemp(prefix="ntp-telemetry-")
stream = os.path.join(tmp, "run.jsonl")
mem = MemorySink()
rec = Recorder(sinks=[JsonlSink(stream), mem])
ledger = []
runner, hist_on = run_once(rec, ledger)
rec.close()

events = load_jsonl(stream)
assert events == list(mem.events()), "JSONL stream != memory ring"

# ---- goodput report == orchestrator accounting (< 0.1 %, equal in fact) ----
doc = report(events)
rows = doc["goodput"]
for pol, row in rows.items():
    assert tuple(sorted(row)) == tuple(sorted(GOODPUT_KEYS)), (pol, row)
total_steps = sum(r["steps"] for r in rows.values())
assert total_steps == STEPS, rows
folded = sum(r["goodput"] * r["steps"] for r in rows.values()) / total_steps
own = runner.goodput()
rel_err = abs(folded - own) / own
assert rel_err < 1e-3, (folded, own)   # acceptance: < 0.1 %
# the boosted policy rows exist: uniform while healthy, ntp_pw degraded
assert set(rows) == {"uniform", "ntp_pw"}, rows

# ---- executed transition spans carry the TransferStats ledger EXACTLY ----
trans = [e for e in events if e["kind"] == "span"
         and e["name"] == "session.transition"]
executed = [e for e in trans if e["attrs"].get("changed") is True]
assert len(executed) == len(ledger) == 4, (len(executed), len(ledger))
for sp, want in zip(executed, ledger):
    assert sp["attrs"]["bytes_moved"] == want["bytes_moved"], (sp, want)
    assert sp["attrs"]["messages"] == want["messages"], (sp, want)
    marks = sp["attrs"]["marks"]
    assert list(marks) == ["planned", "gathered", "repacked", "placed",
                           "executed"], marks
    assert sorted(marks.values()) == list(marks.values()), marks
assert [e["labels"]["kind"] for e in executed] == \
    ["failure", "failure", "repair", "repair"]
# the session's executed-bytes gauge mirrors the span series
gauge = [e["value"] for e in events if e["kind"] == "gauge"
         and e["name"] == "cluster.transition_bytes"
         and e["labels"].get("source") == "executed"]
assert gauge == [w["bytes_moved"] for w in ledger], gauge

# orchestrator.event spans wrap each consumed event with its outcome
oev = [e for e in events if e["kind"] == "span"
       and e["name"] == "orchestrator.event"]
assert len(oev) == 4 and all(e["attrs"]["outcome"] == "applied" for e in oev)

# per-step instrumentation: one step span per optimizer step, analytic
# rel_iter_time recorded whenever a policy decision exists
steps = [e for e in events if e["kind"] == "span"
         and e["name"] == "session.step"]
assert len(steps) == STEPS, len(steps)
# the first step, and the first after each executed transition, pays for
# the step's trace and compile and says so
compiled = [e for e in steps if e["attrs"].get("compiled")]
assert compiled[0] is steps[0], steps[0]
for sp in executed:
    after = next(e for e in steps if e["t0"] >= sp["t1"])
    assert after["attrs"].get("compiled") is True, after
assert len(compiled) == 1 + len(executed), len(compiled)
rel = [e["value"] for e in events if e["kind"] == "gauge"
       and e["name"] == "train.rel_iter_time"
       and e["labels"].get("source") == "analytic"]
assert len(rel) == STEPS and all(r >= 0.0 for r in rel)

# ---- Perfetto export: loadable, same byte counts in the span args ----
trace_path = os.path.join(tmp, "trace.json")
write_chrome_trace(trace_path, events)
with open(trace_path) as f:
    trace = json.load(f)
rows_x = [r for r in trace["traceEvents"]
          if r.get("ph") == "X" and r["name"] == "session.transition"
          and r["args"].get("changed") is True]
assert [r["args"]["bytes_moved"] for r in rows_x] == \
    [w["bytes_moved"] for w in ledger]
lanes = {r["args"]["name"] for r in trace["traceEvents"]
         if r.get("ph") == "M"}   # swimlanes come from SPAN subsystems
assert {"session", "orchestrator"} <= lanes, lanes
tracks = {r["name"] for r in trace["traceEvents"] if r.get("ph") == "C"}
assert any(t.startswith("train.goodput{") for t in tracks), tracks
assert any(t.startswith("cluster.transition_bytes{") for t in tracks), tracks

# ---- recorder-off run is bit-identical ----
_, hist_off = run_once(None)
assert [h["loss"] for h in hist_on] == [h["loss"] for h in hist_off]
assert [h["grad_norm"] for h in hist_on] == [h["grad_norm"] for h in hist_off]
assert telemetry.get() is telemetry.NULL

print(f"goodput: folded {folded:.6f} == runner {own:.6f} "
      f"(rel err {rel_err:.2e}); transitions {len(executed)} "
      f"bytes {[w['bytes_moved'] for w in ledger]}; "
      f"trace rows {len(trace['traceEvents'])}")
print("SESSION_TELEMETRY_OK")
