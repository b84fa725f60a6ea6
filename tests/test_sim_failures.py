"""Failure injection at scheduled virtual times.

A provider or metadata shard goes down mid-scenario at an exact virtual
instant (something wall-clock thread tests can never do reproducibly);
readers must fail over to surviving replicas per the ARCHITECTURE.md
invariants — batched paths re-route only the affected requests, and
``EndpointDown`` surfaces only when every replica of a page/key is gone.
"""

import pytest

from repro.core import BlobSeerService, EndpointDown, Simulator, Wire
from repro.core.scenarios import run_scenario


def test_readers_fail_over_when_provider_dies_mid_scenario():
    r = run_scenario(
        "readers", 32, seed=4,
        n_providers=8, n_meta_shards=4, data_replication=2,
        failures=[(0.004, "prov-0003")],   # mid-read-phase, virtual time
    )
    assert not r.errors, r.errors
    assert r.ops == 32 * 2                 # every read served via failover
    assert r.client_results["chaos-prov-0003"]["killed"] == "prov-0003"


def test_readers_fail_over_when_metadata_shard_dies():
    r = run_scenario(
        "readers", 32, seed=4,
        n_providers=8, n_meta_shards=4, meta_replication=2,
        failures=[(0.004, "meta-0001")],
    )
    assert not r.errors, r.errors
    assert r.ops == 32 * 2


def test_unreplicated_scenario_surfaces_endpoint_down():
    r = run_scenario(
        "readers", 16, seed=4,
        n_providers=4, n_meta_shards=2, data_replication=1,
        failures=[(0.002, "prov-0001")],
        raise_errors=False,
    )
    assert any("EndpointDown" in e for e in r.errors.values()), r.errors


def test_failure_schedule_is_deterministic():
    kw = dict(n_providers=8, n_meta_shards=4, data_replication=2,
              failures=[(0.004, "prov-0003")])
    a = run_scenario("readers", 24, seed=9, **kw)
    b = run_scenario("readers", 24, seed=9, **kw)
    assert a.trace_digest == b.trace_digest
    assert a.rpc == b.rpc


def test_appenders_survive_provider_death_with_replication():
    """Writes keep publishing after a provider dies: store_pages drops the
    dead replica, total order stays contiguous."""
    r = run_scenario(
        "appenders", 24, seed=2,
        n_providers=6, n_meta_shards=3, data_replication=2,
        failures=[(0.003, "prov-0002")],
    )
    assert not r.errors, r.errors
    versions = sorted(
        v for res in r.client_results.values()
        if isinstance(res, dict) for v in res.get("versions", ())
    )
    assert versions == list(range(1, 24 * 2 + 1))


def test_heartbeat_detection_in_virtual_time():
    """Heartbeats age on the virtual clock: a maintenance task detects a
    silent provider deterministically at its scheduled sweep instant."""
    sim = Simulator(seed=0)
    svc = BlobSeerService(n_providers=3, n_meta_shards=2,
                          wire=Wire(clock=sim), heartbeat_timeout=1.0)
    dead = []

    def beat(pid):
        def prog():
            for _ in range(8):
                sim.sleep(0.5)
                svc.pm.get(pid).heartbeat()
        return prog

    def sweeper():
        sim.sleep(2.5)
        dead.extend(svc.pm.check_heartbeats())

    sim.spawn(beat("prov-0000"), name="beat-0")
    sim.spawn(beat("prov-0002"), name="beat-2")
    sim.spawn(sweeper, name="sweeper")   # prov-0001 never beats
    sim.run()
    assert dead == ["prov-0001"]
    assert svc.pm.n_alive() == 2


def test_wal_replayed_stall_detected_under_virtual_clock(tmp_path):
    """A WAL-replayed incomplete update must look stalled on the
    *recovering* VM's clock: with the wall-time default stamp, virtual
    now() minus monotonic would be hugely negative and recovery would
    never fire."""
    from repro.core.version_manager import VersionManager

    wal = str(tmp_path / "vm.wal")
    vm = VersionManager(wal_path=wal)
    bid = vm.create(64, client="c")
    vm.assign_version(bid, None, 64, client="c")  # writer dies here

    sim = Simulator(seed=0)
    vm2 = VersionManager.recover_from_wal(wal, wire=Wire(clock=sim))
    sim.spawn(lambda: sim.sleep(1.0), name="tick")  # virtual time passes
    sim.run()
    stalled = vm2.find_stalled(0.5)
    assert [(b, r.version) for b, r in stalled] == [(bid, 1)]


def test_revived_provider_rejoins_and_serves():
    sim = Simulator(seed=1)
    svc = BlobSeerService(n_providers=4, n_meta_shards=2,
                          wire=Wire(clock=sim), data_replication=2)
    c0 = svc.client("setup")
    bid = c0.create(psize=64)
    v = c0.write(bid, bytes(range(256)), 0)
    out = {}

    def chaos():
        svc.kill_provider("prov-0001")
        sim.sleep(0.01)
        svc.revive_provider("prov-0001")

    def reader():
        c = svc.client("r")
        out["during"] = c.read(bid, v, 0, 256)   # provider down: failover
        sim.sleep(0.02)
        out["after"] = c.read(bid, v, 0, 256)    # provider back
        return True

    sim.spawn(chaos, name="chaos")
    sim.spawn(reader, name="r")
    sim.run()
    assert out["during"] == bytes(range(256))
    assert out["after"] == bytes(range(256))
