"""Host spans, the device trace, and the reduction from both to numbers.

Spans are the benchmark's own, around its calls into each layer: the host
clock gives their lengths, and ``jax.profiler.TraceAnnotation`` writes the
same names into the profiler's trace, so that device idle time can be
attributed to what the host was doing.  The reduction works on plain
event tuples, so a small recorded trace can check it.
"""

from __future__ import annotations

import contextlib
import glob
import os
import time
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax

WINDOW = "bench_traced_window"
DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"

# (plane, line, name, start_ns, duration_ns)
Event = Tuple[str, str, str, float, float]


class Spans:
    """Named host spans, on the host clock and in the profiler's trace."""

    def __init__(self) -> None:
        self.spans: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def durations(self, name: str) -> List[float]:
        return [t1 - t0 for n, t0, t1 in self.spans if n == name]


def capture(fn, workdir: str, keep_names: Iterable[str]):
    """Run ``fn`` under the profiler; returns (fn's result, events).

    The Python tracer stays off.  Only device ops and the host annotations
    named in ``keep_names`` (and the window's own) are kept.
    """
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(workdir, profiler_options=opts):
        with jax.profiler.TraceAnnotation(WINDOW):
            result = fn()
    paths = glob.glob(os.path.join(workdir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {workdir}")
    return result, load_events(max(paths, key=os.path.getmtime),
                               set(keep_names) | {WINDOW})


def op_name(name: str) -> str:
    """An XLA op event's instruction name: ``%fusion.12 = f32[...] ...`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def load_events(path: str, keep_names: set) -> List[Event]:
    """Every event of the device planes' op lines, and the named host spans."""
    data = jax.profiler.ProfileData.from_file(path)
    out: List[Event] = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name in keep_names:
                    out.append((plane.name, line.name,
                                op_name(ev.name) if device else ev.name,
                                float(ev.start_ns), float(ev.duration_ns)))
    return out


# ------------------------------------------------------------------ intervals
def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv for iv in intervals if iv[1] > iv[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged: Sequence[Tuple[float, float]], a: float, b: float) -> float:
    """Length of [a, b) that the merged intervals cover."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged)


def gaps(merged: Sequence[Tuple[float, float]], a: float, b: float):
    out, t = [], a
    for x, y in merged:
        if x > t:
            out.append((t, min(x, b)))
        t = max(t, y)
        if t >= b:
            break
    if t < b:
        out.append((t, b))
    return [(x, y) for x, y in out if y > x]


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                              # mean over devices
    devices: int
    op_s: Dict[str, float]                     # device time by op name, summed over devices
    host: List[Tuple[str, float, float]]       # host spans (name, start_ns, end_ns)
    busy: List[Tuple[float, float]]            # merged busy intervals, first device
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def op_time(self, pattern) -> Optional[float]:
        """Summed device seconds of ops whose name matches ``pattern``; None if none ran."""
        hits = [s for n, s in self.op_s.items() if pattern.search(n)]
        return sum(hits) if hits else None

    def busy_within(self, name: str) -> Tuple[float, float]:
        """(span seconds, device-busy seconds inside them) for host spans ``name``."""
        spans = [(a, b) for n, a, b in self.host if n == name]
        total = sum(b - a for a, b in spans) / 1e9
        busy = sum(covered(self.busy, a, b) for a, b in spans) / 1e9
        return total, busy

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]


def reduce(events: Sequence[Event]) -> TraceSummary:
    win = [(s, s + d) for p, _, n, s, d in events
           if not p.startswith(DEVICE_PLANE_PREFIX) and n == WINDOW]
    if not win:
        raise ValueError("the trace holds no window annotation")
    w0, w1 = win[0]
    per_dev: Dict[str, List[Tuple[float, float]]] = {}
    op_s: Dict[str, float] = {}
    host = []
    for plane, _, name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if plane.startswith(DEVICE_PLANE_PREFIX):
            if b > a:
                per_dev.setdefault(plane, []).append((a, b))
                op_s[name] = op_s.get(name, 0.0) + (b - a) / 1e9
        elif name != WINDOW and b > a:
            host.append((name, a, b))
    merged = {p: merge(iv) for p, iv in per_dev.items()}
    busy_s = (sum(covered(m, w0, w1) for m in merged.values()) / len(merged) / 1e9
              if merged else 0.0)
    first = merged[min(merged)] if merged else []
    summary = TraceSummary(window_s=(w1 - w0) / 1e9, busy_s=busy_s,
                           devices=len(merged), op_s=op_s, host=host, busy=first)
    summary.idle_gaps = _attribute_gaps(first, host, w0, w1)
    return summary


def _attribute_gaps(busy, host, w0, w1, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle stretches, each named by what the host was doing.

    A gap in device activity is cut where a host span starts or ends; each
    piece is named by the innermost span that covers it ("none" where no
    span does), and neighbouring pieces of one name join again.
    """
    out = []
    for a, b in gaps(busy, w0, w1):
        cuts = sorted({a, b} | {t for _, x, y in host for t in (x, y) if a < t < b})
        pieces: List[List] = []
        for x, y in zip(cuts, cuts[1:]):
            mid = (x + y) / 2
            over = [(sy - sx, name) for name, sx, sy in host if sx <= mid < sy]
            name = min(over)[1] if over else "none"
            if pieces and pieces[-1][0] == name:
                pieces[-1][1] += y - x
            else:
                pieces.append([name, y - x])
        out += [(name, length / 1e9) for name, length in pieces]
    return sorted(out, key=lambda kv: -kv[1])[:n]
