"""Reduce one profiler trace (``.xplane.pb``) to what the metrics read.

The traced window is the host span ``bench.window`` that the harness puts
around it. On each TPU device plane the ``XLA Ops`` line gives the
operations that ran: busy time is the union of their intervals inside the
window, idle time the rest. The ``XLA Modules`` line gives each program's
device time and how many of its runs lie in the window. Idle time on
the first device is split over the innermost ``bench.*`` host span at each
moment: what the host was doing while the device waited. The summary also
holds ``trace_scopes.summarize``'s keys: device time by named scope, idle
time by the program's spans, and the step markers.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path
from typing import List, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
TOP = 10


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


LAYOUT = re.compile(r"\{[^{}]*\}")
HLO = re.compile(r"^%[^ ]+ = (\(.*?\)|\S+) ([\w-]+)\(")
CONTROL_FLOW = {"while", "conditional", "call"}


def op_kind(name: str):
    """(opcode, result type) of an op event, whose name on the TPU is the
    HLO instruction's text: ``%fusion.7 = f32[8,128]{1,0} fusion(...)``."""
    m = HLO.match(LAYOUT.sub("", name))
    return (m.group(2), m.group(1)) if m else (None, None)


def op_label(name: str, module: str = "") -> str:
    """A name for an operation that survives recompiles: the program, the
    opcode and the result's shape, without the instance number."""
    code, typ = op_kind(name)
    label = f"{code} {typ}" if code else re.sub(r"[.\d]+$", "", name)
    label = label if len(label) <= 96 else label[:93] + "..."
    return f"{module}:{label}" if module else label


def module_label(name: str) -> str:
    """Program name without the run-specific id: ``jit_prefill(123)`` ->
    ``jit_prefill``."""
    return re.sub(r"\(\d+\)$", "", name)


def _module_at(modules, starts, t):
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2]
    return ""


def _host_segments(spans, lo, hi):
    """[lo, hi) cut into pieces, each labelled with the innermost host span
    over it (the one that began last), or ``host:unannotated``."""
    cuts = sorted({lo, hi, *(t for s in spans for t in s[:2] if lo < t < hi)})
    starts = sorted(spans)
    out, active, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(starts) and starts[i][0] <= a:
            active.append(starts[i])
            i += 1
        active = [s for s in active if s[1] > a]
        label = max(active)[2] if active else "host:unannotated"
        out.append((a, b, label))
    return out


def reduce(path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(Path(path)))
    planes = list(pd.planes)
    host_spans = []
    window = None
    for pl in planes:
        if pl.name != HOST_PLANE:
            continue
        for line in pl.lines:
            for ev in line.events:
                if not ev.name.startswith("bench."):
                    continue
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == WINDOW_SPAN:
                    if window is None or iv[1] - iv[0] > window[1] - window[0]:
                        window = iv
                else:
                    host_spans.append((iv[0], iv[1], ev.name))
    devices = [pl for pl in planes if DEVICE_PLANE.match(pl.name)]
    if window is None or not devices:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span or no device plane")
    lo, hi = window
    window_s = (hi - lo) * 1e-9

    busy_s, op_time, mod_time = [], defaultdict(float), defaultdict(float)
    mod_calls = defaultdict(float)
    first_busy = None
    for k, pl in enumerate(sorted(devices, key=lambda p: p.name)):
        lines = {ln.name: ln for ln in pl.lines}
        mods = []
        if "XLA Modules" in lines:
            for ev in lines["XLA Modules"].events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                mods.append((a, b, module_label(ev.name)))
                c = _clip([(a, b)], lo, hi)
                if c:
                    mod_time[module_label(ev.name)] += \
                        (c[0][1] - c[0][0]) * 1e-9 / len(devices)
                    # a run cut by the window's edge counts by its share
                    # inside, so that time over runs is one run's time
                    mod_calls[module_label(ev.name)] += \
                        (c[0][1] - c[0][0]) / max(b - a, 1) / len(devices)
        mods.sort()
        starts = [m[0] for m in mods]
        op_lines = ([lines["XLA Ops"]] if "XLA Ops" in lines else
                    [ln for n, ln in lines.items() if n != "Steps"])
        ivs = []
        for ln in op_lines:
            for ev in ln.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                c = _clip([(a, b)], lo, hi)
                if not c:
                    continue
                ivs.append(c[0])
                if op_kind(ev.name)[0] in CONTROL_FLOW:
                    continue   # its body's ops are events of their own
                op_time[op_label(ev.name, _module_at(mods, starts, a))] += \
                    (c[0][1] - c[0][0]) * 1e-9 / len(devices)
        merged = _merge(ivs)
        busy_s.append(sum(b - a for a, b in merged) * 1e-9)
        if k == 0:
            first_busy = merged

    gaps = defaultdict(float)
    segs = _host_segments(host_spans, lo, hi)
    j, prev = 0, lo
    for a, b in (first_busy or []) + [(hi, hi)]:
        if a > prev:   # idle from prev to a: split it over the host segments
            while j < len(segs) and segs[j][1] <= prev:
                j += 1
            k = j
            while k < len(segs) and segs[k][0] < a:
                s0, s1, label = segs[k]
                gaps[label] += (min(s1, a) - max(s0, prev)) * 1e-9
                k += 1
        prev = max(prev, b)

    busy = sum(busy_s) / len(busy_s)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    from bench import trace_scopes

    return {
        **trace_scopes.summarize(path),
        "window_s": window_s,
        "busy_s": busy,
        "idle_share": 1.0 - busy / window_s if window_s > 0 else None,
        "devices": len(devices),
        "modules": dict(mod_time),
        "module_calls": dict(mod_calls),
        "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                      "idle_gaps": [[k, v] for k, v in top_gaps]},
    }
