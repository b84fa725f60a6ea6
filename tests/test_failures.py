"""Fault tolerance: replication, failures, stragglers, recovery."""

import os
import time

import pytest

from repro.core import BlobSeerService, EndpointDown
from repro.core.version_manager import VersionManager
import repro.core.blob as blobmod


def test_replicated_read_survives_provider_failure():
    svc = BlobSeerService(n_providers=6, n_meta_shards=4,
                          data_replication=2, meta_replication=2)
    c = svc.client()
    bid = c.create(psize=64)
    v = c.write(bid, bytes(range(256)) * 16, 0)
    svc.kill_provider("prov-0003")
    assert c.read(bid, v, 0, 4096) == bytes(range(256)) * 16


def test_unreplicated_read_fails_after_all_copies_lost():
    svc = BlobSeerService(n_providers=2, n_meta_shards=2, data_replication=1)
    c = svc.client()
    bid = c.create(psize=64)
    v = c.write(bid, b"z" * 1024, 0)
    svc.kill_provider("prov-0000")
    svc.kill_provider("prov-0001")
    with pytest.raises(EndpointDown):
        c.read(bid, v, 0, 1024)


def test_rereplication_restores_fault_tolerance():
    svc = BlobSeerService(n_providers=4, n_meta_shards=2, data_replication=2)
    c = svc.client()
    bid = c.create(psize=64)
    v = c.write(bid, b"q" * 2048, 0)
    # collect locations from metadata
    from repro.core import segment_tree as st
    pd = st.read_meta(svc.dht, c._owner_fn(bid), v,
                      svc.vm.root_pages_published(bid, v), 0, 32)
    locations = {d.page_id: list(d.providers) for d in pd}
    svc.kill_provider("prov-0001")
    moved, losses = svc.pm.rereplicate_from("prov-0001", locations)
    assert moved > 0
    assert losses == []
    for pid, locs in locations.items():
        assert "prov-0001" not in locs
        assert len(locs) == 2


def test_straggler_replica_racing():
    svc = BlobSeerService(n_providers=4, n_meta_shards=2, data_replication=2)
    c = svc.client()
    bid = c.create(psize=64)
    v = c.write(bid, b"s" * 4096, 0)
    svc.make_straggler("prov-0000", 100.0)
    # reads keep working and prefer non-straggler replicas
    assert c.read(bid, v, 0, 4096) == b"s" * 4096


def test_heartbeat_marks_dead_provider():
    svc = BlobSeerService(n_providers=3, n_meta_shards=2,
                          heartbeat_timeout=0.01)
    time.sleep(0.05)
    svc.pm.get("prov-0001").heartbeat()
    dead = svc.pm.check_heartbeats()
    assert "prov-0000" in dead and "prov-0002" in dead
    assert svc.pm.n_alive() == 1


class _DyingClient(blobmod.BlobClient):
    def _build_and_complete(self, blob_id, info, pd_final, **kwargs):
        raise RuntimeError("writer crashed before BUILD_META")


def test_stalled_writer_recovery_unblocks_pipeline():
    svc = BlobSeerService(n_providers=4, n_meta_shards=2)
    c = svc.client()
    bid = c.create(psize=16)
    c.write(bid, b"x" * 64, 0)
    dc = _DyingClient(svc.vm, svc.dht, svc.pm, svc.wire, name="dying")
    with pytest.raises(RuntimeError):
        dc.write(bid, b"y" * 32, 16)
    c.write(bid, b"z" * 16, 0)          # v3, blocked behind dead v2
    assert c.get_recent(bid) == 1
    assert svc.recover_stalled(0.0) == 1
    c.sync(bid, 3, timeout=5)
    assert c.read(bid, 3, 0, 64) == b"z" * 16 + b"y" * 32 + b"x" * 16


def test_monitor_thread_recovers_automatically():
    svc = BlobSeerService(n_providers=4, n_meta_shards=2)
    c = svc.client()
    bid = c.create(psize=16)
    c.write(bid, b"x" * 64, 0)
    dc = _DyingClient(svc.vm, svc.dht, svc.pm, svc.wire, name="dying")
    with pytest.raises(RuntimeError):
        dc.append(bid, b"y" * 32)
    svc.start_monitor(interval=0.05, stall_timeout=0.0)
    try:
        c.sync(bid, 2, timeout=5)
    finally:
        svc.stop_monitor()
    assert c.read(bid, 2, 64, 32) == b"y" * 32


def test_vm_wal_recovery(tmp_path):
    wal = str(tmp_path / "vm.wal")
    svc = BlobSeerService(n_providers=4, n_meta_shards=2, wal_path=wal)
    c = svc.client()
    bid = c.create(psize=32)
    v1 = c.write(bid, b"A" * 100, 0)
    b2 = c.branch(bid, v1)
    c.append(b2, b"B" * 20)
    vm2 = VersionManager.recover_from_wal(wal, wire=svc.wire)
    assert vm2.get_recent(bid) == 1
    assert vm2.get_size(bid, 1) == 100
    assert vm2.get_recent(b2) == 2
    assert vm2.get_size(b2, 2) == 120
    assert vm2.lineage(b2) == ((b2, 1), (bid, 0))


def test_full_service_restart_from_durable_state(tmp_path):
    spool = str(tmp_path / "spool")
    wal = str(tmp_path / "vm.wal")
    svc = BlobSeerService(n_providers=4, n_meta_shards=2,
                          spool_dir=spool, wal_path=wal)
    c = svc.client()
    bid = c.create(psize=32)
    c.write(bid, b"A" * 100, 0)
    c.append(bid, b"B" * 60)
    v = c.get_recent(bid)
    del svc, c
    svc2 = BlobSeerService.restore(spool, wal, n_providers=4, n_meta_shards=2)
    c2 = svc2.client()
    assert c2.get_recent(bid) == v
    assert c2.read(bid, v, 0, 160) == b"A" * 100 + b"B" * 60
    # service keeps working after restart
    v2 = c2.append(bid, b"C" * 10)
    assert c2.read(bid, v2, 150, 20) == b"B" * 10 + b"C" * 10


def test_elastic_provider_join_rebalances():
    svc = BlobSeerService(n_providers=2, n_meta_shards=2)
    c = svc.client()
    bid = c.create(psize=64)
    c.write(bid, b"x" * 64 * 64, 0)
    svc.add_provider("prov-new")
    c.append(bid, b"y" * 64 * 30)
    new = svc.pm.get("prov-new")
    assert new.page_count() > 0
