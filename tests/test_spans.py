"""The program's phase spans (repro.spans) and the save's D2H counter."""

from __future__ import annotations

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.checkpoint.blobckpt import BlobCheckpointer, _nbytes, flatten_with_paths
from repro.core import BlobSeerService, collect_garbage

SAVE_PHASES = {"ckpt.digest", "ckpt.d2h", "ckpt.pack", "ckpt.commit",
               "blob.store_pages", "blob.publish"}


def _since(mark: int):
    """Records of the spans opened after the span id ``mark``."""
    return [r for r in spans.recorded() if r[0] > mark]


def _mark() -> int:
    with spans.span("ckpt.save"):
        pass
    return spans.recorded()[-1][0]


def test_nesting_gives_parent_ids():
    mark = _mark()
    with spans.span("gc.round"):
        with spans.span("gc.mark"):
            pass
        with spans.span("gc.sweep"):
            pass
    recs = {r[2]: r for r in _since(mark)}
    assert [r[2] for r in _since(mark)] == ["gc.mark", "gc.sweep", "gc.round"]
    top = recs["gc.round"]
    assert top[1] is None
    assert recs["gc.mark"][1] == top[0] and recs["gc.sweep"][1] == top[0]
    for name in ("gc.mark", "gc.sweep"):
        _, _, _, t0, t1 = recs[name]
        assert top[3] <= t0 <= t1 <= top[4]
    assert recs["gc.mark"][4] <= recs["gc.sweep"][3]


def test_a_span_that_raises_is_recorded_and_closed():
    mark = _mark()
    with pytest.raises(RuntimeError):
        with spans.span("ckpt.commit"):
            raise RuntimeError("boom")
    with spans.span("ckpt.pack"):
        pass
    (failed, after) = _since(mark)
    assert failed[2] == "ckpt.commit" and after[1] is None


def test_threads_keep_separate_stacks():
    mark = _mark()
    both_open = threading.Barrier(2, timeout=10)

    def worker(outer: str, inner: str) -> None:
        with spans.span(outer):
            both_open.wait()
            with spans.span(inner):
                both_open.wait()

    threads = [threading.Thread(target=worker, args=("gc.round", "gc.mark")),
               threading.Thread(target=worker, args=("ckpt.save", "ckpt.digest"))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    recs = {r[2]: r for r in _since(mark)}
    assert len(recs) == 4
    assert recs["gc.mark"][1] == recs["gc.round"][0]
    assert recs["ckpt.digest"][1] == recs["ckpt.save"][0]
    assert recs["gc.round"][1] is None and recs["ckpt.save"][1] is None


def test_ring_stays_bounded():
    for _ in range(spans.RING_SIZE + 10):
        with spans.span("blob.publish"):
            pass
    recs = spans.recorded()
    assert len(recs) == spans.RING_SIZE
    assert all(isinstance(v, (int, float, str, type(None))) for r in recs[-5:] for v in r)


def test_unknown_name_is_refused():
    mark = _mark()
    with pytest.raises(ValueError, match="unknown span"):
        with spans.span("ckpt.page"):
            pass
    assert _since(mark) == []


def _state():
    return {"w": jnp.arange(3000, dtype=jnp.float32).reshape(30, 100),
            "b": jnp.ones((7,), jnp.bfloat16),
            "step": jnp.zeros((), jnp.int32)}


def _saved_and_collected():
    svc = BlobSeerService(n_providers=2, n_meta_shards=2)
    client = svc.client("trainer")
    ck = BlobCheckpointer(client, psize=1024, header_pages=4)
    client.set_retention(ck.blob_id, keep_last=1)
    state = _state()
    mark = _mark()
    first = ck.save(state, step=0)
    again = ck.save(state, step=1)
    collect_garbage(svc)
    return state, first, again, _since(mark)


def test_save_resave_and_gc_open_only_known_spans():
    _, _, _, recs = _saved_and_collected()
    names = [r[2] for r in recs]
    assert set(names) <= set(spans.SPAN_NAMES)
    assert set(names) == set(spans.SPAN_NAMES)
    assert names.count("ckpt.save") == 2 and names.count("gc.round") == 1
    by_id = {r[0]: r for r in recs}
    for r in recs:
        if r[2] in ("gc.mark", "gc.sweep"):
            assert by_id[r[1]][2] == "gc.round"


def test_save_phases_are_disjoint_children_of_the_save():
    state, _, _, recs = _saved_and_collected()
    saves = [r for r in recs if r[2] == "ckpt.save"]
    for save, n_dirty in zip(saves, (len(flatten_with_paths(state)), 0)):
        kids = sorted((r for r in recs if r[1] == save[0]), key=lambda r: r[3])
        assert {r[2] for r in kids} <= SAVE_PHASES
        assert sum(r[2] == "ckpt.digest" for r in kids) == len(state)
        assert sum(r[2] == "ckpt.d2h" for r in kids) == n_dirty
        for a, b in zip(kids, kids[1:]):
            assert a[4] <= b[3]
        assert save[3] <= kids[0][3] and kids[-1][4] <= save[4]
        # nothing below the phases: no span per page, node or RPC
        assert not [r for r in recs if r[1] in {k[0] for k in kids}]


def test_d2h_bytes_counts_the_dirty_leaves():
    state, first, again, _ = _saved_and_collected()
    assert first.d2h_bytes == sum(_nbytes(x) for x in state.values()) == 12000 + 14 + 4
    assert again.d2h_bytes == 0 and again.pages_written == 0
