"""The readers of the program's own spans: selection, per-save sums, and the
clock they share with the device trace."""

from __future__ import annotations

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import loop, progspans, run
from bench.tests.tiny import run_tiny, tiny_cell
from bench.trace import Spans, TraceSummary, capture, merge, reduce

CELL = "olmo1b-train.ckpt-every-20"
HOST_CLOCK = ["save_digest_s", "save_d2h_s", "save_pack_s", "save_commit_s",
              "store_pages_s", "store_publish_s", "gc_mark_s", "gc_sweep_s"]


@pytest.fixture(autouse=True)
def cpu_kernels(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    monkeypatch.setattr(run, "enable_cache", lambda: None)


def test_tiny_traced_run_reports_the_program_phases(monkeypatch):
    runs, make_run = [], run.Run

    def keep_run(*args, **kwargs):
        runs.append(make_run(*args, **kwargs))
        return runs[-1]

    monkeypatch.setattr(run, "Run", keep_run)
    out = run_tiny(tiny_cell(run.load_cell(CELL)), trace=True)
    assert out["correct"], out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    for name in HOST_CLOCK + ["save_d2h_bytes"]:
        assert name in got, name
        assert got[name] >= 0, name
    # every leaf is dirty after the cycle's steps: the whole state comes over
    (r,) = runs
    assert got["save_d2h_bytes"] == sum(
        int(np.prod(s.shape)) * s.dtype.itemsize for s in jax.tree.leaves(r.job.sys.abstract))
    save_s = np.mean(r.spans.durations("ckpt_save"))
    phases = ["save_digest_s", "save_d2h_s", "save_pack_s", "save_commit_s",
              "store_pages_s", "store_publish_s"]
    assert 0 < sum(got[n] for n in phases) <= save_s
    assert 0 < got["gc_mark_s"] + got["gc_sweep_s"] <= got["gc_s"]
    # idle gaps are named by the program's phases where one covers them
    named = {n for n, _ in out["breakdown"]["idle_gaps"]}
    assert named & set(progspans.program_span_names()), named


def _run(bench_spans, trace=None):
    s = Spans()
    s.spans = list(bench_spans)
    return SimpleNamespace(spans=s, trace=trace)


# (id, parent, name, t0, t1) on the host clock
RECORDS = [
    (1, None, "ckpt.save", 0.5, 1.0),           # set-up's save: before the window
    (3, 2, "ckpt.digest", 10.1, 10.3),
    (4, 3, "blob.publish", 10.15, 10.2),         # a grandchild
    (5, 2, "ckpt.d2h", 10.3, 10.6),
    (2, None, "ckpt.save", 10.05, 10.9),
    (7, 6, "gc.mark", 11.1, 11.4),
    (6, None, "gc.round", 11.0, 11.8),
    (9, 8, "ckpt.digest", 20.2, 20.3),
    (8, None, "ckpt.save", 20.1, 20.5),
    (10, 99, "ckpt.digest", 20.35, 20.4),        # its parent left the ring
]
BENCH_SPANS = [("train_step", 9.0, 10.0), ("ckpt_save", 10.0, 11.0),
               ("gc_round", 11.0, 12.0), ("ckpt_save", 20.0, 21.0)]


def test_groups_select_by_root_inside_the_window_span():
    gs = progspans.groups(BENCH_SPANS, RECORDS, "ckpt_save", "ckpt.save")
    assert [b for b, _ in gs] == [("ckpt_save", 10.0, 11.0), ("ckpt_save", 20.0, 21.0)]
    assert sorted(r[0] for r in gs[0][1]) == [2, 3, 4, 5]
    assert sorted(r[0] for r in gs[1][1]) == [8, 9]
    assert progspans.groups(BENCH_SPANS, RECORDS, "gc_round", "ckpt.save") == []
    assert progspans.groups(BENCH_SPANS, [], "ckpt_save", "ckpt.save") == []


def test_seconds_summed_per_save_and_averaged():
    r = _run(BENCH_SPANS)
    got = progspans.seconds_per(r, "ckpt_save", "ckpt.save", "ckpt.digest", RECORDS)
    assert got == pytest.approx(((10.3 - 10.1) + (20.3 - 20.2)) / 2)
    # a phase a save did not run counts 0 in that save
    got = progspans.seconds_per(r, "ckpt_save", "ckpt.save", "ckpt.d2h", RECORDS)
    assert got == pytest.approx(0.3 / 2)
    got = progspans.seconds_per(r, "gc_round", "gc.round", "gc.mark", RECORDS)
    assert got == pytest.approx(0.3)
    assert progspans.seconds_per(r, "ckpt_save", "ckpt.save", "ckpt.digest", []) is None


def test_clock_map_uses_both_ends():
    # the trace clock in ns: an offset of 7e9 and 0.1% of drift
    def trace_ns(t):
        return 7e9 + t * 1e9 * 1.001

    host = (10.0, 11.0)
    traced = (trace_ns(10.0), trace_ns(11.0))
    for t in (10.0, 10.25, 10.9, 11.0):
        assert progspans.to_trace(t, host, traced) == pytest.approx(trace_ns(t), abs=1e-3)


def _trace(host, busy, devices=1):
    return TraceSummary(window_s=30.0, busy_s=0.0, devices=devices, op_s={},
                        host=host, busy=merge(busy))


def test_program_spans_on_the_trace_clock_and_busy_share():
    def ns(t, k):   # each benchmark span on the trace clock, offset per span
        return (t + k) * 1e9

    host = [("ckpt_save", ns(20.0, 5), ns(21.0, 5)), ("train_step", 0.0, 1.0),
            ("ckpt_save", ns(10.0, 3), ns(11.0, 3))]
    busy = [(ns(10.1, 3), ns(10.2, 3)), (ns(20.25, 5), ns(20.4, 5))]
    r = _run(BENCH_SPANS, _trace(host, busy))
    mapped = progspans.on_trace_clock(r, "ckpt_save", "ckpt.save", RECORDS)
    digest = sorted((a, b) for n, a, b in mapped if n == "ckpt.digest")
    assert digest == [pytest.approx((ns(10.1, 3), ns(10.3, 3))),
                      pytest.approx((ns(20.2, 5), ns(20.3, 5)))]
    share = progspans.busy_share(r, "ckpt_save", "ckpt.save", "ckpt.digest", RECORDS)
    assert share == pytest.approx(100.0 * (0.1 + 0.05) / (0.2 + 0.1))
    # no device plane, no trace, or spans that do not pair: nothing to read
    assert progspans.busy_share(_run(BENCH_SPANS, _trace(host, busy, 0)), "ckpt_save",
                                "ckpt.save", "ckpt.digest", RECORDS) is None
    assert progspans.on_trace_clock(_run(BENCH_SPANS), "ckpt_save", "ckpt.save",
                                    RECORDS) is None
    assert progspans.on_trace_clock(_run(BENCH_SPANS, _trace(host[:1], busy)),
                                    "ckpt_save", "ckpt.save", RECORDS) is None


def test_program_spans_land_on_their_own_trace_events(tmp_path):
    from repro import spans

    bench_spans = Spans()

    def window():
        with bench_spans.span("ckpt_save"):
            with spans.span("ckpt.save"):
                for _ in range(2):
                    with spans.span("ckpt.digest"):
                        jnp.ones(4096).sum().block_until_ready()
                        time.sleep(0.002)
                with spans.span("ckpt.commit"):
                    time.sleep(0.003)

    _, events = capture(window, str(tmp_path), loop.SPAN_NAMES + spans.SPAN_NAMES)
    summary = reduce(events)
    r = SimpleNamespace(spans=bench_spans, trace=summary)
    mapped = sorted(progspans.on_trace_clock(r, "ckpt_save", "ckpt.save"),
                    key=lambda s: s[1])
    own = sorted(((n, a, b) for n, a, b in summary.host if n in spans.SPAN_NAMES),
                 key=lambda s: s[1])
    assert [m[0] for m in mapped] == [o[0] for o in own] == [
        "ckpt.save", "ckpt.digest", "ckpt.digest", "ckpt.commit"]
    for (_, a, b), (_, x, y) in zip(mapped, own):
        assert abs(a - x) < 50e3 and abs(b - y) < 50e3   # ns
