"""What every cell shares: the spec in ``BENCHMARK.json``, loading a cell's
configuration, traffic mix, driver and metric readers by name, the device
and its peaks, compile accounting, the traced window, and the result line.

A cell names a configuration and a traffic mix. The configuration is
``bench/configs/<config>.json`` (its sizes, as run), whose ``model`` names
the module beside it, ``bench/configs/<model>.py`` (weights from the seed,
the plain reference, the operation counts), which configurations of one
model at other sizes share. The traffic mix is
``bench/traffic/<traffic>.json``: parameters, and the ``driver`` that reads
them, ``bench/drivers/<driver>.py``. Each per-layer metric is read by
``bench/metrics/<metric>.py``. Nothing here changes when a cell, a
configuration or a metric is added.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import resource
import shutil
import statistics
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"


def process_start_time() -> float:
    """Wall-clock time (``time.time()``) at which this process started, from
    ``/proc``; the current time where ``/proc`` cannot say."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        hz = os.sysconf("SC_CLK_TCK")
        return time.time() - (uptime - start_ticks / hz)
    except (OSError, ValueError, IndexError):
        return time.time()


def load_module(path: Path, name: Optional[str] = None):
    """Import a file by path (cell files carry names that are not Python
    identifiers, such as ``granite-3-2b.py``)."""
    name = name or "bench_" + "".join(c if c.isalnum() else "_"
                                      for c in path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names loaded."""

    name: str
    chips: int
    config_name: str
    sizes: dict                 # bench/configs/<config>.json
    config: Any                 # bench/configs/<sizes["model"]>.py
    traffic_name: str
    traffic: dict               # bench/traffic/<traffic>.json
    driver: Any                 # bench/drivers/<driver>.py
    end_to_end: List[dict]      # the metrics this cell reports at --trace 0
    per_layer: List[dict]       # ... and at --trace 1


def _reported_in(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, spec: Optional[dict] = None,
              root: Path = ROOT) -> Cell:
    spec = spec if spec is not None else load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[workload]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    return make_cell(
        workload, int(w["chips"]), root / cfg_entry["file"], w["traffic"],
        [m for m in spec["end_to_end"] if _reported_in(m, workload)],
        [m for m in spec["per_layer"] if _reported_in(m, workload)], root)


def make_cell(name: str, chips: int, config_file: Path, traffic: str,
              end_to_end=(), per_layer=(), root: Path = ROOT) -> Cell:
    """A cell from its configuration file and traffic mix, with the model
    module the configuration names and the driver the mix names."""
    config_file = Path(config_file)
    with open(config_file) as f:
        sizes = json.load(f)
    with open(root / "bench" / "traffic" / f"{traffic}.json") as f:
        mix = json.load(f)
    return Cell(
        name=name, chips=chips, config_name=config_file.stem, sizes=sizes,
        config=load_module(config_file.with_name(f"{sizes['model']}.py")),
        traffic_name=traffic, traffic=mix,
        driver=load_module(root / "bench" / "drivers" / f"{mix['driver']}.py"),
        end_to_end=list(end_to_end), per_layer=list(per_layer))


# ---------------------------------------------------------------- seeds

def seed32(seed: int, *stream: int) -> int:
    """A 31-bit integer drawn from ``seed`` (any size) and a stream tag, for
    APIs that take a small seed (``jax.random.PRNGKey``)."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), int(seed) >> 64,
                                 *stream])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) & (2**64 - 1), int(seed) >> 64,
                                *stream]))


# ---------------------------------------------------------------- device

def load_peaks() -> dict:
    with open(BENCH / "peaks.json") as f:
        return json.load(f)


def device_peaks(kind: str) -> dict:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip of ``kind``
    (``device_kind`` as JAX reports it). A kind not in the table is an
    error, never a default."""
    table = load_peaks()["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; bench/peaks.json "
                       f"has {sorted(table)}")
    return table[kind]


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileMeter:
    """Sums JAX's own compile events (as ``chip_smoke.CompileMeter`` does):
    ``backend_compile_duration`` is recorded around every compile-or-load of
    a program, persistent-cache hits included, and each one is counted."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def snapshot(self):
        return (self.compile_s, self.compiles)


def span(name: str, **kw):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name, **kw)


class HostPauses:
    """The longest host pause of a loop, and what the process did in it.
    ``part(name)`` times one named part of an iteration on the wall clock;
    for the longest it keeps the CPU time of the calling thread and of the
    whole process, and the process's major page faults and involuntary
    context switches over it. Python's collector pauses are summed beside.
    A pause that the thread's CPU time nearly fills was work of this
    process; one with little CPU time waited, on the device or on the
    machine."""

    def __init__(self):
        import gc

        self.longest: Optional[dict] = None
        self.gc_s, self.gc_max_s, self._gc_t = 0.0, 0.0, None
        gc.callbacks.append(self._gc)

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            d = time.perf_counter() - self._gc_t
            self.gc_s += d
            self.gc_max_s = max(self.gc_max_s, d)
            self._gc_t = None

    @contextlib.contextmanager
    def part(self, name: str):
        r0 = resource.getrusage(resource.RUSAGE_SELF)
        c0, p0, t0 = time.thread_time(), time.process_time(), \
            time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        if self.longest is None or wall > self.longest["wall_s"]:
            r1 = resource.getrusage(resource.RUSAGE_SELF)
            self.longest = {
                "part": name, "wall_s": wall,
                "thread_cpu_s": time.thread_time() - c0,
                "process_cpu_s": time.process_time() - p0,
                "major_faults": r1.ru_majflt - r0.ru_majflt,
                "involuntary_switches": r1.ru_nivcsw - r0.ru_nivcsw}

    def close(self) -> dict:
        import gc

        gc.callbacks.remove(self._gc)
        return {"longest": self.longest, "gc_s": self.gc_s,
                "gc_max_s": self.gc_max_s}

    @staticmethod
    def describe(rec: dict) -> str:
        x = rec["longest"]
        if not x:
            return "host: no parts timed"
        return (f"host: longest part {x['part']} {x['wall_s']:.3f} s (thread "
                f"cpu {x['thread_cpu_s']:.3f} s, process cpu "
                f"{x['process_cpu_s']:.3f} s, {x['major_faults']} major "
                f"faults, {x['involuntary_switches']} involuntary switches); "
                f"collector {rec['gc_s']:.3f} s, longest {rec['gc_max_s']:.3f} s")


# ---------------------------------------------------------------- checks

@dataclass
class Check:
    """One number compared with the reference, and its limit: the run is
    correct only where ``value`` <= ``limit`` (and is a number)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return (isinstance(self.value, (int, float))
                and math.isfinite(self.value) and self.value <= self.limit)


def load_limits(run) -> Dict[str, float]:
    """Each compared number's limit: ``bench/limits/<workload>.json``."""
    if run.limits is not None:
        return run.limits
    with open(BENCH / "limits" / f"{run.cell.name}.json") as f:
        return json.load(f)["limits"]


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: Optional[Sequence[str]] = None) -> float:
    """Worst leaf of ``|prog - ref|`` over ``max(ref leaf, median ref leaf)``:
    the gap between two norms of each leaf, measured against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    names = list(keep) if keep is not None else list(ref)
    if not names:
        return float("nan")
    med = statistics.median(ref[k] for k in ref)
    gaps = []
    for k in names:
        if k not in prog:
            return float("inf")
        gaps.append(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30))
    return max(gaps)


def moving_leaves(ref_grad: Dict[str, float], share: float = 1e-3):
    """Leaves whose reference gradient is not nought to rounding: at least
    ``share`` of the median leaf's gradient norm."""
    med = statistics.median(ref_grad.values())
    return [k for k, g in ref_grad.items() if g >= share * med]


# ---------------------------------------------------------------- a run

@dataclass
class Outcome:
    """What a driver hands back."""

    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    records: Dict[str, Any]
    checks: List[Check]
    memory_peak_bytes: Optional[int]


@dataclass
class Run:
    """Context of one run, handed to the cell's driver."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_process: float = field(default_factory=time.time)
    meter: Any = None
    setup_s: Optional[float] = None
    _compile_at_setup: Any = None
    trace_bounds: Optional[tuple] = None     # perf_counter (start, stop)
    trace_path: Optional[Path] = None
    limits: Optional[Dict[str, float]] = None  # default: bench/limits/<cell>
    readings: bool = False   # also read the control and the planted faults

    def setup_done(self) -> float:
        """Mark the end of set-up: the first timed step or request follows."""
        self.setup_s = time.time() - self.t_process
        if self.meter is not None:
            self._compile_at_setup = self.meter.snapshot()
        return self.setup_s

    @property
    def setup_compile_s(self) -> Optional[float]:
        return None if self._compile_at_setup is None \
            else self._compile_at_setup[0]

    @contextlib.contextmanager
    def traced(self):
        """Profile the enclosed span when this run traces (``--trace 1``);
        otherwise a no-op. The span is marked ``bench.window`` in the trace."""
        if not self.trace:
            with span("bench.window"):
                yield
            return
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        t0 = time.perf_counter()
        try:
            with span("bench.window"):
                yield
        finally:
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.trace_bounds = (t0, t1)
            found = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
            self.trace_path = found[-1] if found else None


def device_info(devices) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def accelerator_devices(chips: int):
    """The first ``chips`` accelerator devices, or exit non-zero with no
    result: there is no CPU fallback."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu":
        raise SystemExit(f"bench: needs an accelerator; JAX found only "
                         f"platform {platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devices)} {platform} device(s)")
    return devices[:chips]


def configure_compile_cache() -> str:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), keeping every program so that a warm run
    compiles nothing."""
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


def read_metrics(run: Run, outcome: Outcome, summary: Optional[dict]) -> dict:
    """Each per-layer metric of the cell from its reader
    ``bench/metrics/<name>.py``; a reader that finds nothing returns None
    and the metric is left out."""
    out = {}
    for m in run.cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(run, outcome.records, summary)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(run: Run, outcome: Outcome, summary: Optional[dict]) -> dict:
    correct = bool(outcome.checks) and all(c.ok for c in outcome.checks)
    if run.trace:
        metrics = read_metrics(run, outcome, summary)
    else:
        metrics = {}
        for m in run.cell.end_to_end:
            v = outcome.end_to_end.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = device_info(run.devices)
    device["memory_peak_bytes"] = outcome.memory_peak_bytes
    line = {"correct": correct, "attempted": int(outcome.attempted),
            "failed": int(outcome.failed), "metrics": metrics,
            "device": device}
    if run.trace and summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        line["breakdown"] = summary["breakdown"]
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in outcome.checks}
    return line


def print_checks(checks: List[Check]) -> None:
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
