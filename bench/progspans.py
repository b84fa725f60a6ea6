"""The program's own phase spans (``repro.spans``), as the metrics read them.

The program records ``(id, parent_id, name, t0, t1)`` on the host clock
the benchmark's spans use (``time.perf_counter``).  A program span is the
window's when its root ancestor lies inside one of the window's benchmark
spans of a given name (``ckpt_save``, ``gc_round``): ``run.spans`` holds
only the window's spans.  Program times go onto the device trace's clock
through that enclosing benchmark span, whose two ends are in both
records: linear between them, so any offset and drift cancel.

Every function returns None or nothing where there is nothing to read,
as on a program that records no spans.
"""

from __future__ import annotations

from statistics import fmean
from typing import List, Optional, Sequence, Tuple

from bench.trace import covered

# (id, parent_id, name, t0, t1), as repro.spans records it
Record = Tuple[int, Optional[int], str, float, float]
BenchSpan = Tuple[str, float, float]


def program_records() -> List[Record]:
    """The program's recorded spans; none if the program records no spans."""
    try:
        from repro.spans import recorded
    except ImportError:
        return []
    return recorded()


def program_span_names() -> Tuple[str, ...]:
    """The names the program's spans may take; none if it records no spans."""
    try:
        from repro.spans import SPAN_NAMES
    except ImportError:
        return ()
    return tuple(SPAN_NAMES)


def _root(rec: Record, by_id: dict) -> Record:
    while rec[1] is not None and rec[1] in by_id:
        rec = by_id[rec[1]]
    return rec


def groups(bench_spans: Sequence[BenchSpan], records: Sequence[Record],
           bench_name: str, root_name: str) -> List[Tuple[BenchSpan, List[Record]]]:
    """Per benchmark span ``bench_name`` that holds a ``root_name`` program
    span, that span and every program record whose root lies inside it."""
    by_id = {r[0]: r for r in records}
    roots = {}
    for r in records:
        top = _root(r, by_id)
        if top[2] == root_name:
            roots.setdefault(top[0], []).append(r)
    out = []
    for b in bench_spans:
        if b[0] != bench_name:
            continue
        inside = [rec for rid, recs in roots.items()
                  if b[1] <= by_id[rid][3] and by_id[rid][4] <= b[2] for rec in recs]
        if inside:
            out.append((b, inside))
    return out


def window_groups(run, bench_name: str, root_name: str, records=None):
    records = program_records() if records is None else records
    return groups(run.spans.spans, records, bench_name, root_name)


def seconds_per(run, bench_name: str, root_name: str, name: str,
                records=None) -> Optional[float]:
    """Seconds of the ``name`` spans summed within each enclosing benchmark
    span, mean over those spans."""
    gs = window_groups(run, bench_name, root_name, records)
    if not gs:
        return None
    return fmean(sum(r[4] - r[3] for r in recs if r[2] == name) for _, recs in gs)


def to_trace(t: float, host: Tuple[float, float], traced: Tuple[float, float]) -> float:
    """Host-clock ``t`` on the trace clock, between the two ends of one span
    seen on both clocks."""
    (h0, h1), (s0, s1) = host, traced
    return s0 + (t - h0) * (s1 - s0) / (h1 - h0)


def on_trace_clock(run, bench_name: str, root_name: str,
                   records=None) -> Optional[List[Tuple[str, float, float]]]:
    """The window's program spans as ``(name, start_ns, end_ns)`` on the
    trace's clock; None without a trace, or where the benchmark spans of
    the two records cannot be paired one for one."""
    t = run.trace
    if t is None:
        return None
    host = [(a, b) for n, a, b in run.spans.spans if n == bench_name]
    traced = sorted((a, b) for n, a, b in t.host if n == bench_name)
    if not host or len(host) != len(traced):
        return None
    pair = dict(zip(host, traced))
    out = []
    for (_, h0, h1), recs in window_groups(run, bench_name, root_name, records):
        s = pair[(h0, h1)]
        out += [(r[2], to_trace(r[3], (h0, h1), s), to_trace(r[4], (h0, h1), s))
                for r in recs]
    return out


def busy_share(run, bench_name: str, root_name: str, name: str,
               records=None) -> Optional[float]:
    """Device-busy time inside the ``name`` spans over their length, in percent."""
    spans = on_trace_clock(run, bench_name, root_name, records)
    if not spans or not run.trace.devices:
        return None
    mine = [(a, b) for n, a, b in spans if n == name]
    total = sum(b - a for a, b in mine)
    if total <= 0:
        return None
    return 100.0 * sum(covered(run.trace.busy, a, b) for a, b in mine) / total
