"""Durability tier: erasure coding, storage classes, scrub/repair.

Covers the self-healing plane end to end — the RS-style codec itself,
EC blobs through the full write/read path, survival of any ``m``
provider losses (and typed failure at ``m + 1``), the scrub plane's
detect/repair loop (gaps, bitrot, budget deferral), the cold tier with
lifecycle demotion, and the four repair-path bugfix regressions
(rereplicate losses, dedup refresh, ``steps()`` error typing,
``FilePageStore`` fsync/tmp hygiene).
"""

import itertools
import os

import pytest

from repro.core.durability import lifecycle_round, scrub_round
from repro.core.placement import (
    ErasureCodedPolicy,
    ReplicationPolicy,
    ec_decode,
    ec_encode,
    logical_pid,
    page_codec,
    parse_policy,
    shard_id,
    split_shard,
)
from repro.core.provider import PageIntegrityError
from repro.core.service import BlobSeerService
from repro.core.sim import Simulator
from repro.core.transport import EndpointDown, Wire


def _corrupt(prov, pid=None) -> str:
    """Flip one byte of a stored page behind the provider's back
    (digest bookkeeping untouched — silent bitrot)."""
    vic = pid if pid is not None else sorted(prov.store.iter_pids())[0]
    raw = prov.store.get(vic)
    prov.store.delete(vic)
    prov.store.put(vic, bytes([raw[0] ^ 0xFF]) + raw[1:])
    return vic


# ---------------------------------------------------------------- codec


def test_ec_codec_roundtrip_all_loss_patterns():
    payload = bytes(range(256)) * 17 + b"tail"
    for k, m in ((2, 1), (3, 2), (6, 2)):
        shards = ec_encode(payload, k, m)
        assert len(shards) == k + m
        for subset in itertools.combinations(range(k + m), k):
            got = ec_decode([(j, shards[j]) for j in subset], k, m)
            assert got == payload, (k, m, subset)


def test_ec_codec_small_and_unaligned_payloads():
    for length in (0, 1, 5, 6, 7, 4095, 4096, 4097):
        payload = bytes((i * 31) % 256 for i in range(length))
        shards = ec_encode(payload, 6, 2)
        # parity-heavy subset: drop two data shards
        subset = [2, 3, 4, 5, 6, 7]
        assert ec_decode([(j, shards[j]) for j in subset], 6, 2) == payload


def test_ec_codec_insufficient_shards_raises():
    shards = ec_encode(b"x" * 100, 3, 2)
    with pytest.raises(ValueError):
        ec_decode([(0, shards[0]), (1, shards[1])], 3, 2)


def test_policy_parsing_and_page_ids():
    assert parse_policy("rep:3") == ReplicationPolicy(3)
    assert parse_policy("ec:6+2") == ErasureCodedPolicy(6, 2)
    p = parse_policy("ec:4+2")
    assert p.width(1) == 6 and p.tag == "ec4+2"
    from repro.core.pages import fresh_page_id

    pid = fresh_page_id(tag=p.tag)
    assert page_codec(pid) == (4, 2)
    sid = shard_id(pid, 3)
    assert split_shard(sid) == (pid, 3)
    assert logical_pid(sid) == pid
    plain = fresh_page_id()
    assert page_codec(plain) is None
    assert split_shard(plain) is None
    assert logical_pid(plain) == plain


# ------------------------------------------------------- EC blob end-to-end


def _ec_service(n_providers=10, psize=4096, **kw):
    svc = BlobSeerService(n_providers=n_providers, n_meta_shards=2,
                          verify_digests=True, **kw)
    c = svc.client("w")
    bid = c.create(psize=psize)
    svc.set_blob_placement(bid, "ec:6+2")
    return svc, c, bid


def test_ec_blob_write_read_and_overhead():
    svc, c, bid = _ec_service()
    payload = bytes((i * 7) % 256 for i in range(4 * 4096))
    v = c.append(bid, payload)
    assert c.read(bid, v, 0, len(payload)) == payload
    # sub-range reads decode the page once and slice
    assert c.read(bid, v, 5000, 1000) == payload[5000:6000]
    stored = sum(p.stored_bytes() for p in svc.pm.all_providers())
    assert stored / len(payload) <= 1.5  # 8/6 + shard headers


def test_ec_survives_any_m_provider_losses():
    svc, c, bid = _ec_service()
    payload = b"\xa5" * (2 * 4096)
    v = c.append(bid, payload)
    # find one page's shard group and kill any 2 of its 8 providers
    provs = {pid: info[1] for pid, info in svc.vm.page_locations().items()}
    group = next(iter(provs.values()))
    for a, b in ((0, 1), (3, 7), (6, 7)):
        svc.kill_provider(group[a])
        svc.kill_provider(group[b])
        assert svc.client("r").read(bid, v, 0, len(payload)) == payload
        svc.revive_provider(group[a])
        svc.revive_provider(group[b])


def test_ec_typed_failure_past_m_losses():
    svc, c, bid = _ec_service(page_cache_bytes=0)
    payload = b"\x42" * 4096
    v = c.append(bid, payload)
    group = next(iter(svc.vm.page_locations().values()))[1]
    for pid in group[:3]:  # m + 1 = 3 of the 8 shard homes
        svc.kill_provider(pid)
    with pytest.raises(EndpointDown):
        svc.client("r").read(bid, v, 0, len(payload))


def test_ec_placement_requires_width_providers():
    svc = BlobSeerService(n_providers=4, n_meta_shards=2)
    c = svc.client("w")
    bid = c.create(psize=1024)
    with pytest.raises(RuntimeError):
        svc.set_blob_placement(bid, "ec:6+2")


# ------------------------------------------------------------ scrub/repair


def test_scrub_repairs_dead_provider_gaps_and_relocates():
    svc, c, bid = _ec_service()
    payload = bytes((i * 3) % 256 for i in range(3 * 4096))
    v = c.append(bid, payload)
    svc.kill_provider("prov-0000")
    svc.kill_provider("prov-0003")
    stats = svc.scrub()
    assert stats["damaged_pages"] > 0
    assert stats["losses"] == []
    assert svc.scrub()["damaged_pages"] == 0  # converged
    # repaired shards live on NEW providers via the relocation overlay:
    # kill a third original home — decode now needs a relocated shard
    group = next(iter(svc.vm.page_locations().values()))[1]
    alive_homes = [p for p in group if not svc.wire.is_down(p)]
    svc.kill_provider(alive_homes[0])
    assert svc.client("r").read(bid, v, 0, len(payload)) == payload
    assert svc.pm.rpc_counters()["repair_pages"] > 0
    assert svc.pm.rpc_counters()["repair_bytes"] > 0


def test_scrub_detects_and_repairs_corruption_in_place():
    svc = BlobSeerService(n_providers=4, n_meta_shards=2,
                          data_replication=2, verify_digests=True)
    c = svc.client("w")
    bid = c.create(psize=1024)
    v = c.append(bid, b"A" * 4096)
    prov = svc.pm.get("prov-0001")
    vic = _corrupt(prov)
    good = bytes([prov.store.get(vic)[0] ^ 0xFF]) + prov.store.get(vic)[1:]
    # reads fail over past the corrupt copy meanwhile
    assert c.read(bid, v, 0, 4096) == b"A" * 4096
    stats = svc.scrub()
    assert stats["corrupt_copies"] == 1
    assert stats["repaired_pages"] >= 1
    assert prov.store.get(vic) == good  # restored in place
    assert svc.scrub()["damaged_pages"] == 0


def test_scrub_repairs_a_corrupt_copy_of_a_kept_page():
    """Pages kept as views of a handed-over buffer (``given``) are
    digested, corrupted by the chaos target, scrubbed and repaired like
    ``bytes`` pages, and read back unchanged."""
    import numpy as np

    from repro.core.scenarios import apply_failure_target

    svc = BlobSeerService(n_providers=4, n_meta_shards=2,
                          data_replication=2, verify_digests=True)
    c = svc.client("w")
    bid = c.create(psize=1024)
    data = bytes(range(256)) * 16
    v = c.write_many(bid, [(np.frombuffer(data, np.uint8), 0)],
                     given=[True])[0]
    prov = svc.pm.get("prov-0001")
    assert all(type(prov.store.get(q)) is np.ndarray
               for q in prov.store.iter_pids())
    assert svc.scrub()["damaged_pages"] == 0
    apply_failure_target(svc, {}, "corrupt:prov-0001")
    assert c.read(bid, v, 0, len(data)) == data   # fails over meanwhile
    stats = svc.scrub()
    assert stats["corrupt_copies"] == 1 and stats["repaired_pages"] == 1
    assert svc.scrub()["damaged_pages"] == 0
    assert svc.client("r").read(bid, v, 0, len(data)) == data


def test_scrub_budget_defers_and_converges():
    svc = BlobSeerService(n_providers=6, n_meta_shards=2,
                          data_replication=2, verify_digests=True)
    c = svc.client("w")
    bid = c.create(psize=1024)
    c.append(bid, b"B" * 8192)
    svc.kill_provider("prov-0002")
    first = svc.scrub(budget_bytes=3000)
    assert first["repair_bytes"] <= 3000
    if first["damaged_pages"] > first["repaired_pages"]:
        assert first["deferred_pages"] > 0
    for _ in range(16):
        if svc.scrub(budget_bytes=3000)["damaged_pages"] == 0:
            break
    assert svc.scrub(budget_bytes=3000)["damaged_pages"] == 0


def test_scrub_reports_unrecoverable_pages_as_losses():
    svc = BlobSeerService(n_providers=3, n_meta_shards=2,
                          data_replication=1, verify_digests=True)
    c = svc.client("w")
    bid = c.create(psize=1024)
    c.append(bid, b"C" * 2048)
    # replication 1: killing a page's only holder is unrecoverable
    holders = {info[1][0] for info in svc.vm.page_locations().values()}
    for h in holders:
        svc.kill_provider(h)
    stats = svc.scrub()
    assert len(stats["losses"]) == len(svc.vm.page_locations())
    assert stats["repaired_pages"] == 0


def test_read_fails_over_corrupt_replica_typed():
    """verify_digests=True: a corrupt copy raises PageIntegrityError at
    the provider; with no surviving replica the reader sees the typed
    EndpointDown, never silent bad bytes."""
    svc = BlobSeerService(n_providers=2, n_meta_shards=2,
                          data_replication=1, verify_digests=True,
                          page_cache_bytes=0)
    c = svc.client("w")
    bid = c.create(psize=1024)
    v = c.append(bid, b"D" * 1024)
    (pid, (holder, *_rest), _len), = [
        (p, i[1], i[2]) for p, i in svc.vm.page_locations().items()]
    prov = svc.pm.get(holder)
    _corrupt(prov, pid)
    with pytest.raises(PageIntegrityError):
        prov.get_page(pid)
    with pytest.raises(EndpointDown):
        c.read(bid, v, 0, 1024)


# --------------------------------------------------- cold tier + lifecycle


def test_cold_tier_lifecycle_demotion_and_read_through():
    sim = Simulator(seed=0)
    svc = BlobSeerService(n_providers=4, n_meta_shards=2,
                          wire=Wire(clock=sim), n_cold_providers=2,
                          verify_digests=True)

    def prog():
        c = svc.client("w")
        bid = c.create(psize=1024)
        v = c.append(bid, b"E" * 4096)
        svc.set_lifecycle(bid, 0.5)
        assert lifecycle_round(svc)["demoted"] == 0  # too young
        svc.clock.sleep(1.0)
        stats = lifecycle_round(svc)
        assert stats["demoted"] == 4
        cold = [p for p in svc.pm.all_providers() if p.tier == "cold"]
        hot_pages = sum(p.page_count() for p in svc.pm.all_providers()
                        if p.tier == "hot")
        assert sum(p.page_count() for p in cold) == 4
        assert hot_pages == 0
        # S3-class backend bills per request
        assert sum(p.store.op_counts["put"] for p in cold) == 4
        # reads find the demoted pages through the relocation overlay
        assert c.read(bid, v, 0, 4096) == b"E" * 4096
        assert svc.pm.rpc_counters()["locate_lookups"] > 0
        # scrub agrees the cold copies are the expected holders
        assert scrub_round(svc)["damaged_pages"] == 0
        return {"ok": True}

    sim.spawn(prog, name="t")
    sim.run()
    assert sim.results()["t"] == {"ok": True}


def test_cold_pages_promote_back_to_hot_on_repeated_access():
    """ROADMAP item 1 follow-up: demotion is no longer one-way — a cold
    page read ``promote_reads`` times since the last lifecycle pass
    moves back to a hot ring owner, and later passes leave it hot until
    it ages out again."""
    sim = Simulator(seed=0)
    svc = BlobSeerService(n_providers=4, n_meta_shards=2,
                          wire=Wire(clock=sim), n_cold_providers=2,
                          page_cache_bytes=0, verify_digests=True)

    def prog():
        c = svc.client("w")
        bid = c.create(psize=1024)
        v = c.append(bid, b"P" * 4096)
        svc.set_lifecycle(bid, 0.5, promote_reads=3)
        svc.clock.sleep(1.0)
        assert lifecycle_round(svc)["demoted"] == 4
        cold = [p for p in svc.pm.all_providers() if p.tier == "cold"]
        assert sum(p.page_count() for p in cold) == 4

        # below the threshold: the pass leaves everything cold
        assert c.read(bid, v, 0, 1024) == b"P" * 1024
        stats = lifecycle_round(svc)
        assert stats["promoted"] == 0
        assert sum(p.page_count() for p in cold) == 4

        # hammer the first page past the threshold: it promotes, alone
        for _ in range(3):
            assert c.read(bid, v, 0, 1024) == b"P" * 1024
        stats = lifecycle_round(svc)
        assert stats["promoted"] == 1
        # wire-byte convention, like demoted_bytes: cold read + hot put
        assert stats["promoted_bytes"] == 2 * 1024
        assert sum(p.page_count() for p in cold) == 3
        assert svc.pm.rpc_counters()["promoted_pages"] == 1

        # the promoted copy serves from the hot tier and reads back
        assert c.read(bid, v, 0, 4096) == b"P" * 4096
        hot_pages = sum(p.page_count() for p in svc.pm.all_providers()
                        if p.tier == "hot")
        assert hot_pages == 1
        # scrub agrees the post-promotion holders are the real ones
        assert scrub_round(svc)["damaged_pages"] == 0

        # it ages out again once it goes quiet: promotion is a cycle,
        # not a one-shot escape from the lifecycle
        svc.clock.sleep(1.0)
        assert lifecycle_round(svc)["demoted"] == 1
        assert sum(p.page_count() for p in cold) == 4
        assert c.read(bid, v, 0, 4096) == b"P" * 4096
        return {"ok": True}

    sim.spawn(prog, name="t")
    sim.run()
    assert sim.results()["t"] == {"ok": True}


def test_cold_providers_excluded_from_placement():
    svc = BlobSeerService(n_providers=2, n_meta_shards=2,
                          n_cold_providers=2)
    c = svc.client("w")
    bid = c.create(psize=1024)
    c.append(bid, b"F" * 4096)
    for p in svc.pm.all_providers():
        if p.tier == "cold":
            assert p.page_count() == 0


# --------------------------------------------------------------- EC + GC


def test_ec_pages_sweep_and_orphan_scan():
    from repro.core.gc import collect_garbage

    svc, c, bid = _ec_service()
    payload = b"\x33" * 4096
    for _ in range(3):
        c.write(bid, payload, 0)
    c.set_retention(bid, keep_last=1)
    stats = collect_garbage(svc, client="gc", orphan_grace=None)
    assert stats["retired_versions"] == 2
    assert stats["swept_pages"] > 0
    # shard stores hold exactly the kept version's shards; the orphan
    # scan (grace 0) maps shard ids to logical pages and keeps them all
    stats2 = collect_garbage(svc, client="gc", orphan_grace=0.0)
    assert stats2["orphan_pages"] == 0
    v = c.get_recent(bid)
    assert c.read(bid, v, 0, len(payload)) == payload


# ------------------------------------------------ determinism of the plane


def test_durability_scenario_deterministic():
    from repro.core.scenarios import build_env, run_scenario

    def once():
        env = build_env(4, seed=7, ops_per_client=2, scenario="durability")
        return run_scenario(
            "durability", 4, seed=7, env=env,
            failures=[(0.03, "prov-0000"), (0.04, "corrupt:prov-0002")])

    a, b = once(), once()
    assert not a.errors
    assert a.trace_digest == b.trace_digest
    readers = [r for r in a.client_results.values()
               if isinstance(r, dict) and "failed_reads" in r]
    assert sum(r["failed_reads"] for r in readers) == 0


# ------------------------------------------------- satellite regressions


def test_rereplicate_continues_past_unrecoverable_pages():
    """Regression: the sweep used to raise EndpointDown at the first
    page with no serving replica, stranding every later page."""
    svc = BlobSeerService(n_providers=4, n_meta_shards=2,
                          data_replication=2)
    c = svc.client("w")
    bid = c.create(psize=64)
    c.write(bid, b"q" * 2048, 0)
    locations = {pid: list(info[1])
                 for pid, info in svc.vm.page_locations().items()}
    # fabricate an unrecoverable entry that sorts FIRST: its survivor
    # list names a provider that does not hold the page (KeyError path)
    lost_locs = ["prov-0001", "prov-0000"]
    locations["pg-0000-lost"] = list(lost_locs)
    expected = sum(1 for p, locs in locations.items()
                   if p != "pg-0000-lost" and "prov-0001" in locs)
    svc.kill_provider("prov-0001")
    moved, losses = svc.pm.rereplicate_from("prov-0001", locations)
    assert losses == ["pg-0000-lost"]
    assert moved == expected > 0
    for pid, locs in locations.items():
        if pid == "pg-0000-lost":
            continue
        assert "prov-0001" not in locs and len(locs) == 2


def test_rereplicate_refreshes_dedup_providers():
    """Regression: dedup hits used to keep handing out descriptors
    pointing at the dead provider after repair moved the page."""
    svc = BlobSeerService(n_providers=3, n_meta_shards=2,
                          data_replication=1, dedup=True)
    c = svc.client("w")
    bid = c.create(psize=1024)
    c.append_many(bid, [b"G" * 1024], dedup=True)
    pid, (provs, ) = next(((p, (i[1],))
                           for p, i in svc.vm.page_locations().items()))
    dead = provs[0]
    locations = {pid: list(provs)}
    # a second holder so the page survives the kill
    survivor = next(p for p in svc.pm.all_providers()
                    if p.pid != dead and p.tier == "hot")
    survivor.put_pages([(pid, b"G" * 1024)])
    locations[pid].append(survivor.pid)
    svc.kill_provider(dead)
    moved, losses = svc.pm.rereplicate_from(dead, locations)
    assert moved == 1 and losses == []
    assert svc.dedup_index.rpc_counters()["refreshed"] == 1
    # the index now hands out the refreshed location set
    entry = svc.dedup_index._by_digest[svc.dedup_index._by_pid[pid]]
    assert dead not in entry.providers
    assert set(entry.providers) == set(locations[pid])


def test_dedup_refresh_providers_verb():
    svc = BlobSeerService(n_providers=2, n_meta_shards=2, dedup=True)
    c = svc.client("w")
    bid = c.create(psize=1024)
    c.append_many(bid, [b"H" * 1024], dedup=True)
    idx = svc.dedup_index
    pid = next(iter(idx._by_pid))
    n = idx.refresh_providers([(pid, ("prov-0001",)),
                               ("pg-missing", ("prov-0000",))])
    assert n == 1  # unknown ids are skipped, not an error
    ctr = idx.rpc_counters()
    assert ctr["refresh_rounds"] == 1 and ctr["refreshed"] == 1
    assert idx._by_digest[idx._by_pid[pid]].providers == ("prov-0001",)


def test_checkpointer_steps_propagates_wire_errors():
    """Regression: steps() used to catch bare Exception as
    end-of-history — a downed endpoint silently truncated the list."""
    np = pytest.importorskip("numpy")
    from repro.checkpoint.blobckpt import BlobCheckpointer

    svc = BlobSeerService(n_providers=2, n_meta_shards=2,
                          page_cache_bytes=0)
    ckpt = BlobCheckpointer(svc.client("ck"), psize=1024, header_pages=2)
    state = {"w": np.arange(512, dtype=np.int32)}
    ckpt.save(state, step=1)
    state["w"][0] = 99
    ckpt.save(state, step=2)
    assert [s for _v, s in ckpt.steps()] == [1, 2]
    for p in svc.pm.all_providers():
        svc.kill_provider(p.pid)
    with pytest.raises(EndpointDown):
        ckpt.steps()


def test_file_store_fsync_policy_and_tmp_cleanup(tmp_path, monkeypatch):
    from repro.store.file import FilePageStore

    with pytest.raises(ValueError):
        FilePageStore(str(tmp_path / "bad"), fsync="sometimes")

    store = FilePageStore(str(tmp_path / "spool"), fsync="always")
    store.put("pg-1", b"hello")
    assert store.get("pg-1") == b"hello"

    # regression: a failed replace used to leak the .tmp file
    calls = {"n": 0}
    real_replace = os.replace

    def boom(src, dst):
        calls["n"] += 1
        raise OSError("disk full")

    monkeypatch.setattr(os, "replace", boom)
    with pytest.raises(OSError):
        store.put("pg-2", b"x" * 10)
    monkeypatch.setattr(os, "replace", real_replace)
    assert calls["n"] == 1
    leftovers = [f for f in os.listdir(tmp_path / "spool")
                 if f.endswith(".tmp")]
    assert leftovers == []
    assert not store.has("pg-2")
    store.put("pg-2", b"x" * 10)  # store still usable after the failure
    assert store.get("pg-2") == b"x" * 10


def test_service_spool_fsync_threads_through(tmp_path):
    svc = BlobSeerService(n_providers=1, n_meta_shards=2,
                          spool_dir=str(tmp_path), spool_fsync="always")
    prov = svc.pm.get("prov-0000")
    assert prov.store.fsync == "always"
    c = svc.client("w")
    bid = c.create(psize=1024)
    v = c.append(bid, b"I" * 1024)
    assert c.read(bid, v, 0, 1024) == b"I" * 1024
