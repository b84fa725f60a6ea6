"""BlobSeer checkpointing: incremental COW, atomic publish, branch, resume."""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import BlobCheckpointer
from repro.checkpoint.blobckpt import header_pages_for
from repro.core import BlobSeerService
from repro.data import ByteTokenizer, CorpusWriter, ShardedReader


@pytest.fixture
def ckpt_env():
    svc = BlobSeerService(n_providers=6, n_meta_shards=4)
    c = svc.client()
    return svc, c


def _state(seed=0, scale=1.0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": scale * jax.random.normal(k, (600,)),
                   "frozen": jnp.ones((256,), jnp.float32)},
        "step": jnp.asarray(seed, jnp.int32),
    }


def test_save_restore_roundtrip(ckpt_env):
    svc, c = ckpt_env
    ck = BlobCheckpointer(c, psize=256, header_pages=8)
    s = _state(1)
    stats = ck.save(s, step=1)
    assert stats.version >= 1
    got = ck.restore(jax.eval_shape(lambda: s))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_header_sized_from_state_holds_its_manifest(ckpt_env):
    # 4096 pages of 1 KiB: their digests outgrow a 16-page header region
    svc, c = ckpt_env
    psize = 1024
    s = {"w": np.random.default_rng(0).standard_normal(1 << 20, np.float32),
         "step": np.asarray(3, np.int32)}
    with pytest.raises(ValueError, match="exceeds header region"):
        BlobCheckpointer(c, psize=psize, header_pages=16).save(s, step=3)
    header_pages = header_pages_for(s, psize)
    assert header_pages > 16
    ck = BlobCheckpointer(c, psize=psize, header_pages=header_pages)
    assert ck.save(s, step=3).pages_total == 4097
    got = ck.restore(jax.eval_shape(lambda: s))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(s)):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def test_incremental_save_shares_unchanged_pages(ckpt_env):
    svc, c = ckpt_env
    ck = BlobCheckpointer(c, psize=256, header_pages=8)
    s1 = _state(1)
    st1 = ck.save(s1, step=1)
    s2 = dict(s1, step=jnp.asarray(2, jnp.int32))  # only 'step' changes
    st2 = ck.save(s2, step=2)
    assert st2.pages_written < st1.pages_total // 4
    assert st2.sharing_fraction > 0.5


def test_pack_copies_only_the_padded_run(ckpt_env):
    """Runs of whole pages go to the store as views of the D2H buffer;
    only a run whose last page passes its leaf's end is copied to be
    padded, and every save restores bit for bit."""
    svc, c = ckpt_env
    ck = BlobCheckpointer(c, psize=256, header_pages=8)
    k0, k1 = jax.random.split(jax.random.PRNGKey(7))
    state = {"whole": jax.random.normal(k0, (256,)),     # 4 pages exactly
             "tail": jax.random.normal(k1, (70,))}       # 280 B: 2 pages
    like = jax.eval_shape(lambda: state)

    def save_and_check(step, pages, copied):
        stats = ck.save(state, step=step)
        assert (stats.pages_written, stats.pack_copy_bytes) == (pages, copied)
        got = ck.restore(like, version=stats.version)
        for key, leaf in state.items():
            assert got[key].tobytes() == np.asarray(leaf).tobytes()

    save_and_check(0, 6, 2 * 256)       # all dirty: the tail's one run
    state["whole"] = state["whole"].at[130].set(-1.0)
    save_and_check(1, 1, 0)             # tail clean, whole-page run a view
    state["tail"] = state["tail"].at[3].set(-1.0)
    save_and_check(2, 1, 0)             # the tail's first page lies inside
    state["tail"] = state["tail"].at[69].set(-1.0)
    save_and_check(3, 1, 256)           # its last page is padded
    assert ck.save(state, step=4).pack_copy_bytes == 0


def test_old_checkpoints_remain_readable(ckpt_env):
    svc, c = ckpt_env
    ck = BlobCheckpointer(c, psize=256, header_pages=8)
    versions = {}
    for step in range(1, 4):
        s = _state(step, scale=float(step))
        stats = ck.save(s, step=step)
        versions[step] = (stats.version, s)
    for step, (v, want) in versions.items():
        got, mani = ck.restore(jax.eval_shape(lambda: want), version=v,
                               with_manifest=True)
        assert mani["step"] == step
        np.testing.assert_allclose(np.asarray(got["params"]["w"]),
                                   np.asarray(want["params"]["w"]))


def test_branch_forks_lineage(ckpt_env):
    svc, c = ckpt_env
    ck = BlobCheckpointer(c, psize=256, header_pages=8)
    s1 = _state(1)
    st1 = ck.save(s1, step=1)
    child = ck.branch(st1.version)
    sb = _state(9, scale=3.0)
    child.save(sb, step=9)
    s2 = _state(2, scale=2.0)
    ck.save(s2, step=2)
    got_b = child.restore(jax.eval_shape(lambda: sb))
    got_2 = ck.restore(jax.eval_shape(lambda: s2))
    np.testing.assert_allclose(np.asarray(got_b["params"]["w"]),
                               np.asarray(sb["params"]["w"]))
    np.testing.assert_allclose(np.asarray(got_2["params"]["w"]),
                               np.asarray(s2["params"]["w"]))


def test_reader_mid_save_sees_consistent_checkpoint(ckpt_env):
    """Atomic publication: GET_RECENT during a save never yields a torn
    checkpoint — restores resolve either the old or the new manifest."""
    svc, c = ckpt_env
    ck = BlobCheckpointer(c, psize=128, header_pages=8)
    shapes = jax.eval_shape(lambda: _state(0))
    ck.save(_state(1, scale=1.0), step=1)
    errs = []
    stop = threading.Event()

    def reader():
        rc = svc.client("reader")
        rck = BlobCheckpointer(rc, ck.blob_id, header_pages=8)
        while not stop.is_set():
            try:
                got, mani = rck.restore(shapes, with_manifest=True)
                w = np.asarray(got["params"]["w"])
                expect = np.asarray(_state(mani["step"],
                                           scale=float(mani["step"])) ["params"]["w"])
                if not np.allclose(w, expect):
                    errs.append(f"torn checkpoint at step {mani['step']}")
            except Exception as e:
                errs.append(repr(e))

    t = threading.Thread(target=reader)
    t.start()
    for step in range(2, 6):
        ck.save(_state(step, scale=float(step)), step=step)
    stop.set()
    t.join()
    assert not errs, errs[:3]


def test_restart_resumes_delta_detection(ckpt_env):
    svc, c = ckpt_env
    ck = BlobCheckpointer(c, psize=256, header_pages=8)
    s = _state(1)
    ck.save(s, step=1)
    ck2 = BlobCheckpointer(c, ck.blob_id, header_pages=8)
    ck2.load_digest_cache()
    stats = ck2.save(s, step=2)       # identical content
    assert stats.pages_written == 0


def test_manifest_carries_extra_state(ckpt_env):
    svc, c = ckpt_env
    ck = BlobCheckpointer(c, psize=256, header_pages=8)
    ck.save(_state(1), step=1, extra={"reader": {"version": 3, "position": 77,
                                                 "shard": 0, "n_shards": 2}})
    _, mani = ck.restore(jax.eval_shape(lambda: _state(1)), with_manifest=True)
    assert mani["extra"]["reader"]["position"] == 77


def test_pipeline_reader_deterministic_resume(ckpt_env):
    svc, c = ckpt_env
    w = CorpusWriter(c, psize=128)
    tok = ByteTokenizer()
    for i in range(30):
        w.append_tokens(tok.encode(f"doc {i} " + "lorem ipsum " * (i % 7 + 1)))
    r = ShardedReader(c, w.blob_id, batch=2, seq_len=16)
    _ = r.next_batch()
    st = r.state_dict()
    want = [r.next_batch() for _ in range(3)]
    r2 = ShardedReader(c, w.blob_id, batch=2, seq_len=16, state=st)
    got = [r2.next_batch() for _ in range(3)]
    for (a1, b1), (a2, b2) in zip(want, got):
        np.testing.assert_array_equal(a1, a2)
        np.testing.assert_array_equal(b1, b2)


def test_concurrent_ingestion_does_not_disturb_pinned_reader(ckpt_env):
    svc, c = ckpt_env
    w = CorpusWriter(c, psize=128)
    tok = ByteTokenizer()
    for i in range(20):
        w.append_tokens(tok.encode(f"base doc {i} " + "abc " * 20))
    r = ShardedReader(c, w.blob_id, batch=2, seq_len=8)
    pinned = r.state.version
    first = r.next_batch()
    stop = threading.Event()

    def ingest():
        cw = CorpusWriter(svc.client("ingest"), w.blob_id)
        i = 0
        while not stop.is_set():
            cw.append_tokens(tok.encode(f"new doc {i}"))
            i += 1

    t = threading.Thread(target=ingest)
    t.start()
    r_again = ShardedReader(c, w.blob_id, batch=2, seq_len=8,
                            state=dict(version=pinned, position=0,
                                       shard=0, n_shards=1))
    again = r_again.next_batch()
    stop.set()
    t.join()
    np.testing.assert_array_equal(first[0], again[0])


def test_rolling_pin_taken_before_commit_survives_gc_race(ckpt_env):
    """Worst-case interleaving: a retention GC round (keep-last-1) fires
    after every single write RPC of save(). The rolling manifest pin is
    taken while the manifest snapshot is still the newest published
    version — before the commit pointer write — so no round can retire
    the manifest of a just-committed checkpoint."""
    from repro.core import collect_garbage

    svc, c = ckpt_env
    ck = BlobCheckpointer(c, psize=256, header_pages=8)
    c.set_retention(ck.blob_id, keep_last=1)
    orig_write = c.write

    def write_then_gc(bid, buf, off):
        v = orig_write(bid, buf, off)
        collect_garbage(svc, orphan_grace=None)
        return v

    c.write = write_then_gc
    try:
        s = _state(1)
        ck.save(s, step=1)
        got = ck.restore(jax.eval_shape(lambda: s))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(s)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the next save rolls the pin forward under the same race
        s2 = dict(s, step=jnp.asarray(2, jnp.int32))
        ck.save(s2, step=2)
        got2 = ck.restore(jax.eval_shape(lambda: s2))
        np.testing.assert_array_equal(np.asarray(got2["step"]), 2)
    finally:
        c.write = orig_write


def test_failed_commit_releases_fresh_pin(ckpt_env):
    """If the commit-pointer write fails after the rolling pin was
    taken, the pin is released — a failed save() must not leak an
    untimed lease that excludes its manifest snapshot from GC forever."""
    svc, c = ckpt_env
    ck = BlobCheckpointer(c, psize=256, header_pages=8)
    s = _state(1)
    ck.save(s, step=1)
    base = len(svc.vm.pins())
    orig_write = c.write

    def fail_commit(bid, buf, off):
        if off == 0 and len(buf) == 9:  # the commit-pointer record
            raise RuntimeError("injected commit failure")
        return orig_write(bid, buf, off)

    c.write = fail_commit
    try:
        with pytest.raises(RuntimeError):
            ck.save(_state(2, scale=2.0), step=2)
    finally:
        c.write = orig_write
    assert len(svc.vm.pins()) == base  # no orphan lease
    ck.save(_state(3, scale=3.0), step=3)  # next save recovers cleanly
    assert len(svc.vm.pins()) == base
