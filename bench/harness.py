"""What every cell shares: the benchmark's own files found by name, the
device check, the compile cache, the traced window, the per-layer metric
readers, and the result line.

Nothing here imports the program under test; the drivers in
``bench/drivers`` do, and take from it only the system under test.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# the benchmark's files, found by name
# ---------------------------------------------------------------------------

def use_program():
    """Make the program under test importable: its package lives in the
    checkout's ``src``."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    mix: dict               # the traffic mix's parameters
    end_to_end: list        # entries of BENCHMARK.json that this cell reports
    per_layer: list


def reports(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def resolve(bench: dict, workload: str) -> Cell:
    """The cell's configuration, mix and metrics, each by its name."""
    from bench.traffic.gen import load_mix
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if reports(m, workload, names)]
    for m in layer:
        metric_path(m["name"])          # every reader exists
    return Cell(workload, int(w["chips"]), config, load_mix(w["traffic"]),
                e2e, layer)


def metric_path(name: str) -> Path:
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    return path


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", metric_path(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(config: dict):
    """The configuration's plain reference, beside its file."""
    name = config["reference"]
    return importlib.import_module(f"bench.configs.{name}")


# ---------------------------------------------------------------------------
# device, cache, compile clock
# ---------------------------------------------------------------------------

def require_chips(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU, JAX found {devs[0].platform!r}; "
                     f"there is no CPU fallback")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding every program,
    so that a second run of a cell compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Seconds spent getting compiled programs: backend compiles plus
    reads from the persistent cache, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def memory_peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


# ---------------------------------------------------------------------------
# the traced window
# ---------------------------------------------------------------------------

class TracedWindow:
    """Records a profiler trace of part of a run's window when tracing is
    on, and reduces it.  ``start``/``stop`` are called at step
    boundaries, after the device has finished the work before them."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.active = False
        self.done = False
        self.reduced = None
        self._dir = None
        self._span = None
        self.t0 = self.t1 = None

    def start(self):
        import jax
        if not self.enabled or self.active or self.done:
            return
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._dir)
        self._span = span("window")
        self._span.__enter__()
        self.active = True
        self.t0 = time.perf_counter()

    def stop(self):
        import jax
        if not self.active:
            return
        self.t1 = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def reduce(self):
        from bench import reduce as red
        if not self.done:
            return None
        try:
            self.reduced = red.reduce(red.load(red.find_xplane(self._dir)))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return self.reduced


# ---------------------------------------------------------------------------
# the run's outcome
# ---------------------------------------------------------------------------

@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    """What a driver hands back to the harness."""
    e2e: dict[str, float]                  # end-to-end metric -> value
    checks: list[Check]
    attempted: int
    failed: int
    memory_peak_bytes: int | None
    counters: dict = field(default_factory=dict)
    traced: TracedWindow | None = None
    notes: list[str] = field(default_factory=list)


@dataclass(frozen=True)
class Hooks:
    """The harness's marks: set-up ends where the window starts; the
    window ends before the comparison with the reference."""
    setup_done: object
    window_done: object


@dataclass
class Context:
    """What a per-layer metric's reader is given."""
    cell: Cell
    counters: dict
    reduced: object | None
    peaks: object | None


def result_line(cell: Cell, out: Outcome, device: dict, setup_s: float,
                compile_s: float, trace: bool, peaks) -> dict:
    correct = bool(out.checks) and all(c.ok for c in out.checks) \
        and out.failed == 0
    metrics = {}
    breakdown = None
    counters = dict(out.counters, compile_s=compile_s)
    if trace:
        reduced = out.traced.reduced if out.traced is not None else None
        ctx = Context(cell, counters, reduced, peaks)
        for m in cell.per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None:
            device = dict(device, busy_s=reduced.busy_s,
                          window_s=reduced.window_s)
            breakdown = reduced.breakdown()
    else:
        values = dict(out.e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    line = {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics,
            "device": dict(device, memory_peak_bytes=out.memory_peak_bytes)}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
