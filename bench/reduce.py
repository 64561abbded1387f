"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy and idle time, device time by operation and by name
scope, and the idle gaps named by what the host was doing in them.

Only the benchmark's traced run records a trace, with JAX's profiler; the
repository's own tracer stays off, so the traced run executes the same
programs as the timed one.  The harness marks the traced window with a
``bench.window`` host span and wraps its own calls into the program in
``bench.<what>`` spans (``jax.profiler.TraceAnnotation``).
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
OPS_LINES = ("XLA Ops", "Async XLA Ops")


@dataclass(frozen=True)
class Event:
    start_ns: float
    end_ns: float
    name: str
    text: str = ""          # every string stat: scope path, long name
    line: str = ""          # ops nest only within one line


@dataclass
class Trace:
    devices: dict[str, list[Event]] = field(default_factory=dict)
    host: list[Event] = field(default_factory=list)

    def window(self) -> tuple[float, float]:
        spans = [e for e in self.host if e.name == WINDOW_SPAN]
        if len(spans) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                             f"{len(spans)}")
        return spans[0].start_ns, spans[0].end_ns


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def _text(ev) -> str:
    parts = []
    for _, value in ev.stats:
        if isinstance(value, str):
            parts.append(value)
    return " ".join(parts)


def load(path: str) -> Trace:
    """Device ops of every accelerator plane, and the harness's host
    spans, on the trace's common clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name not in OPS_LINES:
                    continue
                for ev in line.events:
                    # "%fusion.3 = bf16[...] fusion(...)" -> "fusion.3"
                    name = ev.name.split(" = ", 1)[0].lstrip("%")
                    ops.append(Event(ev.start_ns, ev.start_ns
                                     + ev.duration_ns, name, _text(ev),
                                     line.name))
            if ops:
                tr.devices[plane.name] = sorted(ops, key=lambda e: e.start_ns)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        tr.host.append(Event(ev.start_ns, ev.start_ns
                                             + ev.duration_ns, ev.name))
    return tr


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append((s, t))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Disjoint sorted cover of the intervals."""
    merged: list[list[float]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

@dataclass
class Reduced:
    window_s: float
    busy_s: float                        # mean over devices
    n_devices: int
    op_s: dict[str, float]               # name -> self seconds, device mean
    idle_by_host: dict[str, float]       # host span -> idle seconds (mean)
    events: list[tuple[str, str, float]]  # (name, text, seconds in window)

    def time_where(self, pred) -> float:
        """Seconds, mean over devices, of the ops for which
        ``pred(name, text)`` holds."""
        return sum(s for n, t, s in self.events if pred(n, t)) \
            / self.n_devices

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def self_times(events, lo: float, hi: float):
    """Each op's time inside [lo, hi] less the time of the ops nested in
    it, for the events of one line (a ``while`` op and the ops of its body
    are events of one line)."""
    out = []
    stack: list[list] = []          # [event, end, own]
    for e in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        while stack and stack[-1][1] <= s:
            out.append(tuple(stack.pop()[::2]))
        if stack:
            stack[-1][2] -= max(0.0, min(t, stack[-1][1]) - s)
        stack.append([e, t, t - s])
    out.extend(tuple(x[::2]) for x in reversed(stack))
    return out


def _innermost(host: list[Event], t: float) -> str:
    best = None
    for e in host:
        if e.name != WINDOW_SPAN and e.start_ns <= t < e.end_ns:
            if best is None or e.end_ns - e.start_ns < \
                    best.end_ns - best.start_ns:
                best = e
    return best.name if best is not None else "no harness span"


def reduce(tr: Trace) -> Reduced:
    """Everything inside the ``bench.window`` span."""
    lo, hi = tr.window()
    if not tr.devices:
        raise ValueError("the trace holds no device operations")
    n = len(tr.devices)
    busy = 0.0
    op_s: dict[str, float] = defaultdict(float)
    events = []
    idle: dict[str, float] = defaultdict(float)
    marks = sorted({x for e in tr.host for x in (e.start_ns, e.end_ns)})
    for ops in tr.devices.values():
        inside = [e for e in ops if e.end_ns > lo and e.start_ns < hi]
        cover = union(clip(inside, lo, hi))
        busy += sum(t - s for s, t in cover)
        for line in sorted({e.line for e in inside}):
            for e, own in self_times([e for e in inside if e.line == line],
                                     lo, hi):
                op_s[e.name] += own * 1e-9 / n
                events.append((e.name, e.text, own * 1e-9))
        edges = [lo] + [x for iv in cover for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            # split the gap where a host span starts or ends, and name
            # each piece by the innermost span over it
            cuts = [a] + marks[bisect.bisect_right(marks, a):
                               bisect.bisect_left(marks, b)] + [b]
            for x, y in zip(cuts, cuts[1:]):
                if y > x:
                    idle[_innermost(tr.host, (x + y) / 2)] += \
                        (y - x) * 1e-9 / n
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n,
                   n_devices=n, op_s=dict(op_s), idle_by_host=dict(idle),
                   events=events)


COLLECTIVE_OPS = ("all-to-all", "collective-permute", "all-gather",
                  "all-reduce", "reduce-scatter")


def is_exchange_op(name: str, text: str) -> bool:
    """An XLA collective (``all-to-all.3`` in HLO, ``all_to_all.3`` in a
    TPU trace), or an op under one of the factorized schedule's
    ``a2a_round[<axis>]`` name scopes."""
    name = name.replace("_", "-")
    return any(op in name for op in COLLECTIVE_OPS) or "a2a_round[" in text
