"""Scale-out write plane: lineage sharding, batched writer verbs,
pipelined weave.

Covers the PR-5 contracts:

* per-lineage locks — publication on blob B proceeds while blob A's
  lineage lock is held / while blob A's writer is stalled
  pre-``metadata_complete`` (cross-blob publication independence);
* ``assign_versions_many`` / ``metadata_complete_many`` amortize
  version-manager round trips and show up in ``rpc_report()``;
* ``append_many`` / ``write_many`` produce byte-identical state to
  their sequential equivalents, including the unaligned-append
  phase-2 re-stripe and intra-batch boundary merges;
* WAL records carry lineage ids and recovery rebuilds the shard
  layout;
* the ``append_burst`` scenario replays deterministically.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from repro.core import BlobSeerService
from repro.core import blob as blobmod
from repro.core.gc import collect_orphans
from repro.core.scenarios import run_scenario
from repro.core.transport import Wire
from repro.core.version_manager import VersionManager


# ---------------------------------------------------------------------------
# Lineage sharding / cross-blob publication independence
# ---------------------------------------------------------------------------


def test_lineages_are_disjoint_and_branches_join_parent():
    svc = BlobSeerService(n_providers=2, n_meta_shards=2)
    c = svc.client()
    a = c.create(psize=16)
    b = c.create(psize=16)
    assert svc.vm.lineage_id(a) != svc.vm.lineage_id(b)
    c.write(a, b"x" * 32, 0)
    br = c.branch(a, 1)
    assert svc.vm.lineage_id(br) == svc.vm.lineage_id(a)
    # distinct lineages really are distinct lock domains
    assert svc.vm._shard_of(a) is svc.vm._shard_of(br)
    assert svc.vm._shard_of(a) is not svc.vm._shard_of(b)


def test_publication_on_b_proceeds_while_a_lineage_lock_held():
    """Structural independence: a task squatting on blob A's lineage
    critical section cannot delay an assignment+publication on blob B
    (pre-PR, one global VM lock serialized every verb)."""
    svc = BlobSeerService(n_providers=2, n_meta_shards=2)
    c = svc.client()
    a = c.create(psize=16)
    b = c.create(psize=16)

    done = threading.Event()

    def write_b():
        w = svc.client("writer-b")
        w.write(b, b"y" * 32, 0)
        done.set()

    with svc.vm._shard_of(a).lock:          # a "slow writer" on A's lineage
        t = threading.Thread(target=write_b, daemon=True)
        t.start()
        assert done.wait(timeout=10.0), (
            "blob B's write blocked on blob A's lineage lock"
        )
        t.join(timeout=5.0)
    assert c.get_recent(b) == 1


class _CrashBeforeWeave(blobmod.BlobClient):
    def _build_and_complete(self, blob_id, info, pd_final, **kwargs):
        raise RuntimeError("writer crashed before BUILD_META")


def test_stalled_writer_on_a_does_not_block_publication_on_b():
    """Behavioral independence (the ISSUE's regression test): blob A has
    an assigned-but-incomplete update stalling ITS publication pipeline;
    blob B keeps assigning and publishing normally."""
    svc = BlobSeerService(n_providers=4, n_meta_shards=2)
    c = svc.client()
    a = c.create(psize=16)
    b = c.create(psize=16)
    c.write(a, b"a" * 32, 0)

    dc = _CrashBeforeWeave(svc.vm, svc.dht, svc.pm, svc.wire, name="dying")
    with pytest.raises(RuntimeError):
        dc.write(a, b"A" * 16, 0)           # v2 on A: assigned, never complete

    # A is stalled pre-metadata_complete; B publishes freely
    for i in range(3):
        c.append(b, bytes([i + 1]) * 16)
        assert c.get_recent(b) == i + 1
    c.sync(b, 3, timeout=5.0)
    assert c.get_recent(a) == 1             # A still stalled
    assert svc.recover_stalled(0.0) == 1    # recovery completes A's v2
    c.sync(a, 2, timeout=5.0)
    assert c.read(a, 2, 0, 16) == b"A" * 16


def test_sync_timeout_on_stalled_blob_while_other_lineage_publishes():
    """A SYNC waiter of blob A times out on A's own shard condition even
    as blob B's lineage publishes continuously (no cross-lineage
    wakeups needed, none relied on)."""
    svc = BlobSeerService(n_providers=2, n_meta_shards=2)
    c = svc.client()
    a = c.create(psize=16)
    b = c.create(psize=16)
    c.append(b, b"z" * 16)
    with pytest.raises(TimeoutError):
        c.sync(a, 1, timeout=0.05)
    c.append(b, b"z" * 16)
    assert c.get_recent(b) == 2


# ---------------------------------------------------------------------------
# Batched writer verbs
# ---------------------------------------------------------------------------


def test_batched_verbs_amortize_vm_round_trips():
    svc = BlobSeerService(n_providers=4, n_meta_shards=2)
    c = svc.client()
    bid = c.create(psize=16)
    svc.reset_rpc_counters()
    vs = c.append_many(bid, [b"q" * 16] * 8)
    rep = svc.rpc_report()
    # one assign batch + one complete batch for the whole burst
    assert rep["vm_assign_batches"] == 1
    assert rep["vm_complete_batches"] == 1
    assert rep["vm_round_trips"] == 2
    assert rep["vm_ops"] == 16 and rep["vm_batched_ops"] == 16
    assert vs == list(range(1, 9))
    assert c.get_recent(bid) == 8


def test_assign_versions_many_routes_across_lineages():
    svc = BlobSeerService(n_providers=2, n_meta_shards=2)
    c = svc.client()
    a = c.create(psize=16)
    b = c.create(psize=16)
    infos = svc.vm.assign_versions_many(
        [(a, None, 16, ()), (b, None, 32, ()), (a, None, 16, ())],
        client="t",
    )
    assert [i.version for i in infos] == [1, 1, 2]
    assert infos[2].offset == 16            # saw the first request's append
    assert infos[2].recent_updates == ((1, 0, 1),)
    svc.vm.metadata_complete_many([(a, 1), (a, 2), (b, 1)], client="t")
    # publication is per blob, batched completion included
    assert svc.vm.get_recent(a) == 2 and svc.vm.get_recent(b) == 1


def test_assign_versions_many_is_atomic_on_validation_failure():
    """A batch containing an invalid request assigns NOTHING — no
    half-assigned updates left stalling a publication pipeline."""
    from repro.core import WriteBeyondEnd

    svc = BlobSeerService(n_providers=2, n_meta_shards=2)
    c = svc.client()
    a = c.create(psize=16)
    b = c.create(psize=16)
    with pytest.raises(WriteBeyondEnd):
        svc.vm.assign_versions_many(
            [(b, None, 16, ()),          # valid, listed first
             (a, 999, 16, ())],          # WRITE far beyond a's size 0
            client="t",
        )
    # neither blob saw an assignment; both stay fully usable
    assert svc.vm.version_bounds(a) == (0, 0)
    assert svc.vm.version_bounds(b) == (0, 0)
    assert c.append(b, b"x" * 16) == 1
    c.sync(b, 1, timeout=5.0)
    # validation runs against the batch's own running size: an append
    # extending the blob makes a later in-batch write offset legal
    infos = svc.vm.assign_versions_many(
        [(a, None, 32, ()), (a, 16, 16, ())], client="t")
    assert [i.version for i in infos] == [1, 2]
    assert infos[1].offset == 16


def test_append_many_matches_sequential_appends():
    def build(batched: bool):
        svc = BlobSeerService(n_providers=4, n_meta_shards=2)
        c = svc.client()
        bid = c.create(psize=16)
        bufs = [b"a" * 40, b"b" * 7, b"c" * 16, b"d" * 100]
        if batched:
            vs = c.append_many(bid, bufs)
        else:
            vs = [c.append(bid, b) for b in bufs]
        v = c.get_recent(bid)
        return vs, c.read(bid, v, 0, c.get_size(bid, v))

    vs_a, data_a = build(True)
    vs_b, data_b = build(False)
    assert vs_a == vs_b == [1, 2, 3, 4]
    assert data_a == data_b


def test_write_many_boundary_merge_intra_batch():
    svc = BlobSeerService(n_providers=4, n_meta_shards=2)
    c = svc.client()
    bid = c.create(psize=16)
    c.write(bid, b"x" * 64, 0)
    vs = c.write_many(bid, [(b"y" * 10, 5), (b"z" * 20, 60), (b"w" * 3, 12)])
    assert vs == [2, 3, 4]
    ref = bytearray(b"x" * 64 + b"\0" * 16)
    ref[5:15] = b"y" * 10
    ref[60:80] = b"z" * 20
    ref[12:15] = b"w" * 3
    got = c.read(bid, 4, 0, c.get_size(bid, 4))
    assert got == bytes(ref)
    # every intermediate snapshot is independently readable (weave ok)
    assert c.read(bid, 2, 0, 64) == b"x" * 5 + b"y" * 10 + b"x" * 49


_VIEW_BUFS = [bytes(range(40)), b"b" * 7, bytes(range(100, 133)), b"d" * 48]
# item 0 unaligned over the base; item 1 inside item 0 (an intra-batch
# overlap merged from the batch's own buffer); item 2 extends the blob
# from an unaligned offset; item 3 rewrites whole pages of items 0 and 1
_VIEW_OFFSETS = [5, 30, 64, 16]


def _batch_through(verb: str, as_views: bool):
    svc = BlobSeerService(n_providers=4, n_meta_shards=2)
    c = svc.client()
    bid = c.create(psize=16)
    c.write(bid, b"x" * 70, 0)              # unaligned size: appends re-stripe
    sources = [np.frombuffer(b, np.uint8).copy() for b in _VIEW_BUFS]
    if as_views:
        # read-only numpy-backed views, the last one left writable
        bufs = [memoryview(a).toreadonly() for a in sources[:-1]]
        bufs.append(memoryview(sources[-1]))
    else:
        bufs = list(_VIEW_BUFS)
    if verb == "write_many":
        vs = c.write_many(bid, list(zip(bufs, _VIEW_OFFSETS)))
    else:
        vs = c.append_many(bid, bufs)
    for a in sources:                        # the caller reuses its buffers
        a.fill(0xEE)
    snapshots = [c.read(bid, v, 0, c.get_size(bid, v)) for v in vs]
    pages = [p.store.get(pid) for p in svc.pm.all_providers()
             for pid in p.store.iter_pids()]
    return vs, snapshots, pages


@pytest.mark.parametrize("verb", ["write_many", "append_many"])
def test_batched_verbs_take_byte_views(verb):
    """A memoryview item stores and reads back exactly what the same
    bytes do, and every stored page is its own ``bytes`` copy."""
    vs_b, snaps_b, _ = _batch_through(verb, as_views=False)
    vs_v, snaps_v, pages = _batch_through(verb, as_views=True)
    assert vs_v == vs_b == [2, 3, 4, 5]
    assert snaps_v == snaps_b
    assert pages and all(type(p) is bytes for p in pages)
    if verb == "append_many":
        assert snaps_v[-1] == b"x" * 70 + b"".join(_VIEW_BUFS)
    else:
        ref = bytearray(b"x" * 70 + b"\0" * 27)
        for b, off in zip(_VIEW_BUFS, _VIEW_OFFSETS):
            ref[off:off + len(b)] = b
        assert snaps_v[-1] == bytes(ref)


def test_mixed_append_write_batch_rejected():
    svc = BlobSeerService(n_providers=2, n_meta_shards=2)
    c = svc.client()
    bid = c.create(psize=16)
    with pytest.raises(ValueError):
        c._update_many(bid, [(b"a" * 16, None), (b"b" * 16, 0)])


# ---------------------------------------------------------------------------
# Unaligned-append restripe (phase-2 re-stripe rule)
# ---------------------------------------------------------------------------


def test_single_append_unaligned_restripe_content_and_orphans():
    svc = BlobSeerService(n_providers=4, n_meta_shards=2)
    c = svc.client()
    bid = c.create(psize=16)
    c.append(bid, b"a" * 10)                # size 10: next base unaligned
    v = c.append(bid, b"b" * 40)            # optimistic striping was wrong
    assert c.read(bid, v, 0, 50) == b"a" * 10 + b"b" * 40
    # the optimistically stored full pages became orphans: stored page
    # replicas exceed the journaled descriptors
    referenced = svc.vm.all_page_ids()
    stored = sum(p.page_count() for p in svc.pm.all_providers())
    assert stored > len(referenced)
    # the GC orphan inventory reclaims them (zero grace for the test)
    stats = collect_orphans(svc, grace=0.0)
    assert stats["orphan_pages"] == stored - len(referenced)
    assert sum(p.page_count() for p in svc.pm.all_providers()) == len(referenced)
    assert c.read(bid, v, 0, 50) == b"a" * 10 + b"b" * 40


def test_append_many_unaligned_restripe():
    svc = BlobSeerService(n_providers=4, n_meta_shards=2)
    c = svc.client()
    bid = c.create(psize=16)
    c.append(bid, b"s" * 13)                # unaligned burst base
    vs = c.append_many(bid, [b"1" * 40, b"2" * 7, b"3" * 33])
    assert vs == [2, 3, 4]
    expect = b"s" * 13 + b"1" * 40 + b"2" * 7 + b"3" * 33
    assert c.read(bid, 4, 0, len(expect)) == expect
    # intermediate versions too (burst members published in order)
    assert c.read(bid, 2, 0, 53) == b"s" * 13 + b"1" * 40
    assert c.read(bid, 3, 0, 60) == b"s" * 13 + b"1" * 40 + b"2" * 7


# ---------------------------------------------------------------------------
# WAL lineage ids + recovery
# ---------------------------------------------------------------------------


def test_wal_records_carry_lineage_ids_and_recovery_rebuilds_shards(tmp_path):
    import json

    wal = str(tmp_path / "wal")
    vm = VersionManager(wire=Wire(), wal_path=wal)
    a = vm.create(16, client="t")
    b = vm.create(16, client="t")
    vm.assign_versions_many([(a, None, 16, ()), (b, None, 16, ())], client="t")
    vm.metadata_complete_many([(a, 1), (b, 1)], client="t")
    br = vm.branch(a, 1, client="t")
    vm.assign_version(br, None, 16, client="t")

    with open(wal) as f:
        recs = [json.loads(line) for line in f]
    assert all("lineage" in r for r in recs)
    by_blob = {r["blob"]: r["lineage"] for r in recs if "blob" in r}
    assert by_blob[a] == a and by_blob[b] == b and by_blob[br] == a

    vm2 = VersionManager.recover_from_wal(wal)
    assert vm2.lineage_id(br) == a
    assert vm2.lineage_id(b) == b
    assert vm2.get_recent(a) == 1 and vm2.get_recent(b) == 1
    assert vm2.known_blobs() == [a, b, br]
    base, last = vm2.version_bounds(br)
    assert (base, last) == (1, 2)
    assert not vm2.update_log(br, 2).complete  # in-flight update survived


def test_recovered_manager_keeps_publishing_per_lineage(tmp_path):
    wal = str(tmp_path / "wal")
    spool = str(tmp_path / "spool")
    svc = BlobSeerService(n_providers=4, n_meta_shards=2, wal_path=wal,
                          spool_dir=spool)
    c = svc.client()
    a = c.create(psize=16)
    b = c.create(psize=16)
    c.append_many(a, [b"1" * 16, b"2" * 16])
    c.append(b, b"3" * 32)

    svc2 = BlobSeerService.restore(spool, wal, n_providers=4, n_meta_shards=2)
    c2 = svc2.client()
    assert c2.read(a, 2, 0, 32) == b"1" * 16 + b"2" * 16
    assert c2.read(b, 1, 0, 32) == b"3" * 32
    # the recovered shards stay independent and writable
    assert svc2.vm.lineage_id(a) != svc2.vm.lineage_id(b)
    assert c2.append(a, b"4" * 16) == 3
    assert c2.read(a, 3, 16, 32) == b"2" * 16 + b"4" * 16


# ---------------------------------------------------------------------------
# Simulator determinism of the burst scenario
# ---------------------------------------------------------------------------


def test_append_burst_same_seed_identical_digest():
    r1 = run_scenario("append_burst", 24, seed=11, ops_per_client=2)
    r2 = run_scenario("append_burst", 24, seed=11, ops_per_client=2)
    assert r1.trace_digest == r2.trace_digest
    assert r1.rpc == r2.rpc
    assert not r1.errors
    # total appends = n_clients * ops_per_client * BURST
    assert r1.ops == 24 * 2 * 4


def test_append_burst_under_simulator_beats_singles_on_vm_rpcs():
    rb = run_scenario("append_burst", 32, seed=5, ops_per_client=2)
    rs = run_scenario("appenders", 32, seed=5, ops_per_client=2)
    burst_per_op = rb.rpc["vm_round_trips"] / rb.ops
    single_per_op = rs.rpc["vm_round_trips"] / rs.ops
    assert single_per_op / burst_per_op >= 2.0
