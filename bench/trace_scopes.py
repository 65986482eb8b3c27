"""Device time by the program's named scopes, and idle time by its spans.

    python3 bench/trace_scopes.py TRACE.xplane.pb
    python3 bench/trace_scopes.py --workload <name> --seed <n> --seconds <s>

The first form reduces a recorded trace; the second runs a cell traced, as
``bench/run.py --trace 1`` does, and reduces its trace. Both print one JSON
object: ``trace_reduce.reduce``'s summary, which holds this module's keys,
and the per-layer numbers read from them (``layers``).

A device operation's layer is in its event metadata: the ``tf_op`` stat
holds the HLO ``op_name`` path (``jit(train_step)/transpose(jvp(attention))/
dot_general:``), in which each ``jax.named_scope`` is one component.
``jax.profiler.ProfileData`` does not expose metadata stats, so this module
reads the ``.xplane.pb`` itself: plain protobuf wire format, ``XSpace.planes``
(1) > ``XPlane.lines`` (3) > ``XLine.events`` (4), with the plane's
``event_metadata`` (4) and ``stat_metadata`` (5) maps.
"""
from __future__ import annotations

import argparse
import json
import re
import struct
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402

SCOPES = ("embed", "attention", "mlp", "norm", "loss_head", "optimizer")
UNSCOPED = "unscoped"
PASSES = ("forward", "backward", "other")
# the program's host spans (repro.telemetry) and the harness's: dotted
# lower-case words such as ``session.step``, ``data.batch``, ``bench.wait``
# (not the CPU backend's op events, ``dot.279``)
PROGRAM_SPAN = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")
STEP_MARKER = "step_num"


# ------------------------------------------------------------ wire format

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i=0, end=None):
    """(field number, value) of each field of one message: an int for a
    varint or fixed field, a memoryview for a length-delimited one."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire == 1:
            v, i = struct.unpack_from("<q", buf, i)[0], i + 8
        elif wire == 5:
            v, i = struct.unpack_from("<i", buf, i)[0], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield key >> 3, v


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names):
    """(name, value) of one ``XStat``: a ``ref_value`` (7) names another
    stat metadata entry, whose name is the value."""
    sid, value = None, None
    for f, v in _fields(buf):
        if f == 1:
            sid = v
        elif f == 2:
            value = struct.unpack("<d", struct.pack("<q", v))[0]
        elif f in (3, 4):
            value = _signed(v) if f == 4 else v
        elif f in (5, 6):
            value = bytes(v).decode("utf-8", "replace")
        elif f == 7:
            value = stat_names.get(v, "")
    return stat_names.get(sid, ""), value


def _map_entry(buf):
    key, value = 0, None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf):
    """One ``XPlane``: its name, lines (name, [(start_ns, end_ns,
    metadata_id, stat bufs)]), event metadata (id -> (name, stat bufs))
    and stat metadata names."""
    name, lines, ev_meta, stat_names = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            k, m = _map_entry(v)
            mname, stats = "", []
            for g, w in _fields(m):
                if g == 2:
                    mname = bytes(w).decode("utf-8", "replace")
                elif g == 5:
                    stats.append(w)
            ev_meta[k] = (mname, stats)
        elif f == 5:
            k, m = _map_entry(v)
            stat_names[k] = next((bytes(w).decode() for g, w in _fields(m)
                                  if g == 2), "")
    out = []
    for lb in lines:
        lname, ts_ns, events = "", 0, []
        for f, v in _fields(lb):
            if f == 2:
                lname = bytes(v).decode()
            elif f == 3:
                ts_ns = _signed(v)
            elif f == 4:
                mid, off_ps, dur_ps, stats = 0, 0, 0, []
                for g, w in _fields(v):
                    if g == 1:
                        mid = w
                    elif g == 2:
                        off_ps = _signed(w)
                    elif g == 3:
                        dur_ps = w
                    elif g == 4:
                        stats.append(w)
                a = ts_ns + off_ps * 1e-3
                events.append((a, a + dur_ps * 1e-3, mid, stats))
        out.append((lname, events))
    return name, out, ev_meta, stat_names


def read_planes(path):
    """Every plane of the trace at ``path`` (see `_plane`)."""
    buf = memoryview(Path(path).read_bytes())
    return [_plane(v) for f, v in _fields(buf) if f == 1]


# ------------------------------------------------------------ attribution

_WRAPPED = re.compile(r"^(?:[\w-]+\()*([^()]*)\)*$")


def scope_of(op_name: str):
    """(scope, pass) of an HLO ``op_name`` path: the first known scope read
    from the root, or ``unscoped``; ``backward`` under a ``transpose(...)``
    (the recomputed forward of a rematerialised block included), ``forward``
    under a ``jvp(...)``, else ``other``."""
    path = op_name.rsplit(":", 1)[0] if ":" in op_name else op_name
    scope = UNSCOPED
    for part in path.split("/"):
        m = _WRAPPED.match(part)
        if m and m.group(1) in SCOPES:
            scope = m.group(1)
            break
    phase = ("backward" if "transpose(" in path else
             "forward" if "jvp(" in path else "other")
    return scope, phase


def summarize(path) -> dict:
    """The keys this module adds to ``trace_reduce.reduce``'s summary:

    ``scopes``: device seconds inside the ``bench.window`` span per scope
    and pass (``unscoped`` for operations outside every scope), averaged
    over the devices; operations are counted as ``reduce`` counts
    ``breakdown.device_ops``: control-flow wrappers skipped, clipped to the
    window. ``idle_gaps_program``: idle seconds on the first device by the
    innermost host span, the program's spans and the harness's alike.
    ``steps``: profiler step markers that begin inside the window.
    ``program_spans``: how many of each program span begin inside it."""
    planes = read_planes(path)
    spans, markers, window = [], [], None
    for name, lines, meta, stat_names in planes:
        if name != tr.HOST_PLANE:
            continue
        for _, events in lines:
            for a, b, mid, stats in events:
                ename = meta.get(mid, ("", []))[0]
                if ename == tr.WINDOW_SPAN:
                    if window is None or b - a > window[1] - window[0]:
                        window = (a, b)
                elif PROGRAM_SPAN.match(ename):
                    spans.append((a, b, ename))
                elif any(_stat(s, stat_names)[0] == STEP_MARKER
                         for s in stats):
                    markers.append(a)
    devices = sorted((p for p in planes if tr.DEVICE_PLANE.match(p[0])),
                     key=lambda p: p[0])
    if window is None or not devices:
        raise ValueError(f"{path}: no {tr.WINDOW_SPAN} span or no device "
                         "plane")
    lo, hi = window
    counts = defaultdict(int)
    for a, _, ename in spans:
        counts[ename] += lo <= a < hi

    scopes = defaultdict(lambda: dict.fromkeys(PASSES, 0.0))
    first_busy = None
    for k, (_, lines, meta, stat_names) in enumerate(devices):
        names = {ln for ln, _ in lines}
        op_lines = [evs for ln, evs in lines
                    if ln == "XLA Ops" or ("XLA Ops" not in names
                                           and ln != "Steps")]
        tf_op = {}
        for mid, (_, stats) in meta.items():
            for s in stats:
                sname, value = _stat(s, stat_names)
                if sname == "tf_op":
                    tf_op[mid] = value
        ivs = []
        for events in op_lines:
            for a, b, mid, _ in events:
                c = tr._clip([(a, b)], lo, hi)
                if not c:
                    continue
                ivs.append(c[0])
                if tr.op_kind(meta.get(mid, ("", []))[0])[0] in \
                        tr.CONTROL_FLOW:
                    continue
                scope, phase = scope_of(tf_op.get(mid) or "")
                scopes[scope][phase] += \
                    (c[0][1] - c[0][0]) * 1e-9 / len(devices)
        if k == 0:
            first_busy = tr._merge(ivs)

    gaps = defaultdict(float)
    segs = tr._host_segments(spans, lo, hi)
    j, prev = 0, lo
    for a, b in first_busy + [(hi, hi)]:
        if a > prev:
            while j < len(segs) and segs[j][1] <= prev:
                j += 1
            i = j
            while i < len(segs) and segs[i][0] < a:
                s0, s1, label = segs[i]
                gaps[label] += (min(s1, a) - max(s0, prev)) * 1e-9
                i += 1
        prev = max(prev, b)
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:tr.TOP]
    return {"scopes": {k: dict(v) for k, v in scopes.items()},
            "idle_gaps_program": [[k, v] for k, v in top_gaps],
            "steps": len({a for a in markers if lo <= a < hi}),
            "program_spans": {k: n for k, n in counts.items() if n}}


def layers(summary: dict) -> dict:
    """The per-layer numbers of a training cell, from a summary that holds
    ``reduce``'s keys and `summarize`'s: device milliseconds per step of
    attention, the MLP, the loss head and the optimizer (all passes), and
    the scoped share of the busy device time, in %."""
    scopes, steps = summary["scopes"], summary["steps"]
    total = {k: sum(v.values()) for k, v in scopes.items()}
    out = {}
    if steps:
        for k in ("attention", "mlp", "loss_head", "optimizer"):
            out[f"{k}_ms"] = 1e3 * total.get(k, 0.0) / steps
    if summary.get("busy_s"):
        scoped = sum(v for k, v in total.items() if k != UNSCOPED)
        out["scoped_share"] = 100.0 * scoped / summary["busy_s"]
    return out


def reduce_all(path) -> dict:
    summary = tr.reduce(path)
    summary["layers"] = layers(summary)
    return summary


def _run_cell(workload: str, seed: int, seconds: float) -> dict:
    """One traced run of a cell, as ``bench/run.py --trace 1`` makes it,
    reduced by `reduce_all`."""
    from bench import harness

    t_process = harness.process_start_time()
    cell = harness.load_cell(workload)
    devices = harness.accelerator_devices(cell.chips)
    harness.configure_compile_cache()
    run = harness.Run(cell=cell, seed=seed, seconds=seconds, trace=True,
                      devices=devices, t_process=t_process,
                      meter=harness.CompileMeter())
    outcome = cell.driver.run(run)
    if run.trace_path is None:
        raise SystemExit("bench: the profiler wrote no trace")
    summary = reduce_all(run.trace_path)
    harness.shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    summary["train_tokens_per_s"] = outcome.end_to_end.get(
        "train_tokens_per_s")
    summary["correct"] = all(c.ok for c in outcome.checks)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?", help="a recorded .xplane.pb")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    if (args.trace is None) == (args.workload is None):
        ap.error("give either a trace file or --workload")
    summary = (reduce_all(args.trace) if args.trace else
               _run_cell(args.workload, args.seed, args.seconds))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
