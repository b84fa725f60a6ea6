"""The yardstick: trace reduction, peaks, FLOP and byte counts, digests."""

from __future__ import annotations

import json
import re
from pathlib import Path

import numpy as np
import pytest

from bench import flops, reference
from bench.families import olmo
from bench.peaks import peak_for
from bench.trace import WINDOW, covered, gaps, merge, reduce

DATA = Path(__file__).resolve().parent / "data"
CFG = json.loads((Path(__file__).resolve().parents[1]
                  / "configs" / "olmo1b-l4.train-state.json").read_text())


def _brute_busy(intervals, w0, w1, step):
    """Busy time by sampling the window on a grid (independent of merge())."""
    t = np.arange(w0, w1, step)
    hit = np.zeros(t.shape, bool)
    for a, b in intervals:
        hit |= (t >= a) & (t < b)
    return hit.sum() * step


def test_union_and_gaps_by_hand():
    iv = [(0, 10), (5, 20), (30, 40), (39, 41), (50, 50)]
    m = merge(iv)
    assert m == [(0, 20), (30, 41)]   # an empty interval covers nothing
    assert covered(m, 0, 100) == 31
    assert covered(m, 15, 35) == 10
    assert gaps(m, 0, 100) == [(20, 30), (41, 100)]
    assert gaps(m, 10, 35) == [(20, 30)]


def test_reduce_small_recorded_trace():
    events = [tuple(e) for e in json.loads((DATA / "trace_excerpt.json").read_text())]
    s = reduce(events)
    (w0, w1), = [(e[3], e[3] + e[4]) for e in events if e[2] == WINDOW]
    dev = [(max(e[3], w0), min(e[3] + e[4], w1)) for e in events
           if e[0].startswith("/device:") and e[3] < w1 and e[3] + e[4] > w0]
    assert s.window_s == pytest.approx((w1 - w0) / 1e9)
    assert s.devices == 1
    assert s.busy_s == pytest.approx(_brute_busy(dev, w0, w1, 100.0) / 1e9, rel=0.02)
    assert 0 < s.busy_s < s.window_s
    # kernel time by name: the sum of that op's clipped durations
    names = {e[2] for e in events if e[0].startswith("/device:")}
    name = sorted(names)[0]
    want = sum(min(e[3] + e[4], w1) - max(e[3], w0) for e in events
               if e[2] == name and e[0].startswith("/device:")
               and e[3] < w1 and e[3] + e[4] > w0) / 1e9
    assert s.op_time(re.compile("^" + re.escape(name) + "$")) == pytest.approx(want)
    assert s.op_time(re.compile("no-such-kernel")) is None
    # idle gaps: named by a host span, longest first, no longer than the window
    assert s.idle_gaps and all(g <= s.window_s for _, g in s.idle_gaps)
    assert [g for _, g in s.idle_gaps] == sorted((g for _, g in s.idle_gaps), reverse=True)
    assert {n for n, _ in s.idle_gaps} <= {e[2] for e in events} | {"none"}


def test_flops_per_token_by_hand():
    # 4 layers: attention 4·2048² and SwiGLU 3·2048·8192 each, plus the
    # 50304×2048 tied table: 371,458,048 parameters
    assert olmo.n_params(CFG) == 4 * (4 * 2048 * 2048 + 3 * 2048 * 8192) + 50304 * 2048
    assert olmo.n_params(CFG) == 371_458_048
    assert olmo.train_flops_per_token(CFG, 2048) == 6 * 371_458_048 + 12 * 4 * 2048 * 2048


def test_digest_bytes_by_hand():
    # 3 whole pages and one byte: 4 pages of 4096 words, 8 bytes out each
    assert flops.digest_bytes([3 * 16384 + 1], 16384) == 4 * (16384 + 8)
    # a 1 KiB page pads its 256 words to 512
    assert flops.digest_bytes([1024, 10], 1024) == 2 * (2048 + 8)


def test_unknown_device_kind_raises():
    assert peak_for("TPU v5 lite").hbm_bytes_s == 819e9
    with pytest.raises(KeyError, match="no published peak"):
        peak_for("TPU v99")


@pytest.mark.parametrize("page_bytes", [1024, 16384])
def test_reference_digest_matches_the_host_twin(page_bytes):
    from repro.kernels.hostdigest import host_page_digest
    rng = np.random.default_rng(5)
    raw = rng.integers(0, 256, 3 * page_bytes + 100, dtype=np.uint8)
    pages = np.arange(4)
    got = reference.page_digests(raw, pages, page_bytes)
    for p in pages:
        want = host_page_digest(raw[p * page_bytes:(p + 1) * page_bytes].tobytes(), page_bytes)
        assert tuple(int(x) for x in got[p]) == want
