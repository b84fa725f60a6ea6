"""The immutability-aware read-path cache hierarchy.

Covers ``core/cache.py`` (byte-budgeted PageCache LRU, single-flight
de-duplication, the promoted NodeCache) and its integration under
``ProviderManager.fetch_pages``: shared hits across clients, replica
load-balancing, sibling-page prefetch (fire-and-forget + arrival
gating), GC/eviction coherence (retire-intent and sweep hooks), and the
determinism of the cached schedule under the Simulator.
"""

import pytest

from repro.core import (
    BlobSeerService,
    NodeCache,
    PageCache,
    RetiredVersion,
    Simulator,
    Wire,
)
from repro.core.dht import MetadataDHT
from repro.core.gc import collect_garbage
from repro.core.scenarios import run_scenario

PSIZE = 1024
CHUNK = 4 * PSIZE


# ---------------------------------------------------------------------------
# PageCache unit behavior
# ---------------------------------------------------------------------------


def test_page_cache_byte_budget_lru():
    pc = PageCache(budget_bytes=10)
    for i in range(4):
        pc.fill((f"p{i}", 0, 3), b"abc")   # fill without claim: pure insert
    assert len(pc) == 3 and pc.used_bytes() == 9   # 4th insert evicted p0
    assert pc.evictions == 1
    assert "p0" not in pc.cached_page_ids()
    # touching an entry protects it from eviction (true LRU order)
    hits, _, _ = pc.claim([("p1", 0, 3)])
    assert hits[("p1", 0, 3)][0] == b"abc"
    pc.fill(("p4", 0, 3), b"xyz")
    assert "p1" in pc.cached_page_ids() and "p2" not in pc.cached_page_ids()
    # an entry larger than the whole budget is never cached
    pc.fill(("big", 0, 99), b"z" * 99)
    assert "big" not in pc.cached_page_ids()


def test_page_cache_disabled_at_zero_budget():
    pc = PageCache(0)
    assert not pc.enabled
    pc.fill(("p", 0, 3), b"abc")
    assert len(pc) == 0


def test_page_cache_single_flight_claim_protocol():
    pc = PageCache(1 << 20)
    hits, leaders, waiters = pc.claim([("p", 0, 4)])
    assert not hits and leaders == [("p", 0, 4)] and not waiters
    # second claimant of an in-flight key becomes a waiter
    _, l2, w2 = pc.claim([("p", 0, 4)])
    assert not l2 and w2 == [("p", 0, 4)]
    pc.fill(("p", 0, 4), b"data")
    assert pc.wait(("p", 0, 4))[0] == b"data"
    # abandon releases the claim so the next claimant leads
    _, l3, _ = pc.claim([("q", 0, 4)])
    assert l3
    pc.abandon(("q", 0, 4))
    _, l4, _ = pc.claim([("q", 0, 4)])
    assert l4 == [("q", 0, 4)]


def test_page_cache_invalidate_dooms_inflight_fill():
    pc = PageCache(1 << 20)
    pc.fill(("res", 0, 3), b"abc")
    _, leaders, _ = pc.claim([("fly", 0, 3)])
    assert leaders
    assert pc.invalidate_pages(["res", "fly"]) == 1   # one resident entry
    assert pc.cached_page_ids() == set()
    # the in-flight fetch was doomed: its fill is discarded
    pc.fill(("fly", 0, 3), b"abc")
    assert pc.cached_page_ids() == set()


# ---------------------------------------------------------------------------
# NodeCache promotion + counter surfacing
# ---------------------------------------------------------------------------


def test_node_cache_promoted_and_counted():
    svc = BlobSeerService(n_providers=4, n_meta_shards=4)
    c = svc.client()
    bid = c.create(psize=PSIZE)
    c.append(bid, b"n" * CHUNK)
    v = c.get_recent(bid)
    c.read(bid, v, 0, CHUNK)
    c.read(bid, v, 0, CHUNK)      # re-descends the same tree: node hits
    rep = svc.rpc_report()
    assert rep["node_cache_hits"] > 0
    assert rep["node_cache_hit_bytes"] > 0
    # hits are mirrored into the DHT's cache-hit-vs-RPC accounting
    assert rep["dht_get_keys_cached"] == rep["node_cache_hits"]
    svc.reset_rpc_counters()
    assert svc.rpc_report()["node_cache_hits"] == 0


def test_node_cache_standalone_counters():
    dht = MetadataDHT(Wire(), 4)
    cache = NodeCache(dht)
    cache.put(("k", 1), {"v": 1})
    assert cache.get(("k", 1)) == {"v": 1}
    assert cache.get(("k", 2)) is None
    ctr = cache.counters()
    assert ctr["hits"] == 1 and ctr["misses"] == 1
    assert ctr["hit_bytes"] == dht.node_nbytes


# ---------------------------------------------------------------------------
# fetch_pages integration: shared hits, single-flight, balancing, prefetch
# ---------------------------------------------------------------------------


def _preloaded(n_chunks=4, **kwargs):
    svc = BlobSeerService(n_providers=8, n_meta_shards=4, **kwargs)
    c = svc.client("setup")
    bid = c.create(psize=PSIZE)
    for i in range(n_chunks):
        c.append(bid, bytes([i + 1]) * CHUNK)
    return svc, bid, c.get_recent(bid)


def test_cache_shared_across_clients():
    svc, bid, v = _preloaded()
    a, b = svc.client("a"), svc.client("b")
    want = a.read(bid, v, 0, CHUNK)
    svc.reset_rpc_counters()
    assert b.read(bid, v, 0, CHUNK) == want
    rep = svc.rpc_report()
    assert rep["provider_read_pages"] == 0          # pure cache hits
    assert rep["page_cache_hits"] == CHUNK // PSIZE
    assert rep["wire_local_hit_bytes"] == CHUNK


def test_cache_is_page_granular_for_overlapping_subranges():
    """A resident whole page serves any overlapping smaller read; the
    same bytes are never cached twice under different sub-range keys."""
    svc, bid, v = _preloaded()
    c = svc.client("r")
    c.read(bid, v, 0, PSIZE)                     # caches page 0 whole
    svc.reset_rpc_counters()
    assert c.read(bid, v, 0, PSIZE // 2) == bytes([1]) * (PSIZE // 2)
    assert c.read(bid, v, 16, 64) == bytes([1]) * 64
    rep = svc.rpc_report()
    assert rep["provider_read_pages"] == 0       # both served from cache
    assert rep["page_cache_hits"] == 2
    # one entry per page id, not one per sub-range
    assert len(svc.page_cache) == len(svc.page_cache.cached_page_ids())


def test_cache_disabled_service_fetches_every_time():
    svc, bid, v = _preloaded(page_cache_bytes=0)
    c = svc.client("r")
    c.read(bid, v, 0, CHUNK)
    svc.reset_rpc_counters()
    c.read(bid, v, 0, CHUNK)
    rep = svc.rpc_report()
    assert rep["provider_read_pages"] == CHUNK // PSIZE
    assert rep["page_cache_hits"] == 0


def test_single_flight_dedups_concurrent_readers():
    sim = Simulator(seed=5)
    # 4 providers, as in the benchmark deployments: the ring then gives
    # each of the 4 pages its own provider, so the readers' fetches of
    # different pages overlap and a later reader arrives while an
    # earlier one's fetch of the same page is still in flight
    svc = BlobSeerService(n_providers=4, n_meta_shards=4,
                          wire=Wire(clock=sim))
    setup = svc.client("setup")
    bid = setup.create(psize=PSIZE)
    setup.append(bid, b"\xaa" * CHUNK)
    v = setup.get_recent(bid)
    svc.reset_rpc_counters()

    def reader(i):
        def prog():
            c = svc.client(f"r{i}")
            assert c.read(bid, v, 0, CHUNK) == b"\xaa" * CHUNK
            return {"ops": 1}
        return prog

    for i in range(8):
        sim.spawn(reader(i), name=f"r{i}")
    sim.run()
    rep = svc.rpc_report()
    # 8 concurrent readers of the same 4 pages: each page fetched ONCE
    assert rep["provider_read_pages"] == CHUNK // PSIZE
    assert rep["page_cache_inflight_waits"] > 0     # somebody really waited


def test_replica_load_balancing_spreads_cold_read():
    svc = BlobSeerService(n_providers=4, n_meta_shards=2,
                          data_replication=2, page_cache_bytes=0)
    c = svc.client()
    bid = c.create(psize=PSIZE)
    v = c.write(bid, b"r" * PSIZE * 16, 0)
    svc.reset_rpc_counters()
    c.read(bid, v, 0, PSIZE * 16)
    served = {p.pid: svc.wire.stats(p.pid).requests
              for p in svc.pm.all_providers()}
    # outstanding-bytes balancing routes work to every replica holder,
    # not just each page's primary
    assert all(n > 0 for n in served.values()), served


def test_prefetch_hides_sequential_latency():
    def makespan(prefetch):
        sim = Simulator(seed=11)
        svc = BlobSeerService(n_providers=8, n_meta_shards=4,
                              wire=Wire(clock=sim),
                              read_prefetch_pages=prefetch)
        setup = svc.client("setup")
        bid = setup.create(psize=PSIZE)
        for i in range(8):
            setup.append(bid, bytes([i + 1]) * CHUNK)
        v = setup.get_recent(bid)

        def prog():
            c = svc.client("seq")
            for k in range(8):
                assert c.read(bid, v, k * CHUNK, CHUNK) == bytes([k + 1]) * CHUNK
            return {"ops": 8}

        sim.spawn(prog, name="seq")
        sim.run()
        return sim.now(), svc.rpc_report()

    t0, rep0 = makespan(0)
    t1, rep1 = makespan(CHUNK // PSIZE)
    assert rep1["page_cache_prefetch_fills"] > 0
    assert t1 < t0, f"prefetch did not hide latency: {t0} -> {t1}"
    # correctness is asserted inside the programs (bytes compared)


def test_prefetch_never_past_blob_end():
    svc, bid, v = _preloaded(n_chunks=2, read_prefetch_pages=64)
    c = svc.client("tail")
    size = c.get_size(bid, v)
    assert c.read(bid, v, size - PSIZE, PSIZE) == bytes([2]) * PSIZE


def test_prefetch_serves_unaligned_reads():
    """Prefetch-enabled clients fetch whole pages and slice locally, so
    a prefetched page serves a later NON-page-aligned read too."""
    svc, bid, v = _preloaded(read_prefetch_pages=CHUNK // PSIZE)
    c = svc.client("unaligned")
    want0 = bytes([1]) * (CHUNK - 16) + bytes([2]) * 16
    assert c.read(bid, v, 16, CHUNK) == want0          # prefetches chunk 2
    svc.reset_rpc_counters()
    want1 = bytes([2]) * (CHUNK - 16) + bytes([3]) * 16
    assert c.read(bid, v, CHUNK + 16, CHUNK) == want1
    rep = svc.rpc_report()
    # pages 5..8 were prefetched (whole pages) by the first read; the
    # second unaligned read is served from cache except its own last
    # boundary page (index 8) which the first prefetch window missed
    assert rep["page_cache_hits"] >= CHUNK // PSIZE


def test_prefetch_probe_does_not_inflate_hit_counters():
    svc, bid, v = _preloaded(read_prefetch_pages=CHUNK // PSIZE)
    c = svc.client("seq")
    c.read(bid, v, 0, CHUNK)
    c.read(bid, v, 0, CHUNK)   # re-read: prefetch probes find residents
    rep = svc.rpc_report()
    # hits == pages actually served to the reader (4 on the re-read,
    # plus the arrival-gated prefetched none on the first); probe
    # claims of already-resident siblings count nothing
    assert rep["page_cache_hits"] == CHUNK // PSIZE


def test_prefetch_skips_metadata_widening_when_cache_disabled():
    svc, bid, v = _preloaded(page_cache_bytes=0, read_prefetch_pages=8)
    c = svc.client("r")
    svc.reset_rpc_counters()
    c.read(bid, v, 0, CHUNK)
    keys_disabled = svc.rpc_report()["dht_get_keys"]
    svc2, bid2, v2 = _preloaded(page_cache_bytes=0, read_prefetch_pages=0)
    c2 = svc2.client("r")
    svc2.reset_rpc_counters()
    c2.read(bid2, v2, 0, CHUNK)
    # no cache to land prefetches in => no widened descent, same keys
    assert keys_disabled == svc2.rpc_report()["dht_get_keys"]


# ---------------------------------------------------------------------------
# GC / cache coherence
# ---------------------------------------------------------------------------


def test_sweep_evicts_cached_pages_and_read_raises_retired():
    svc, bid, v = _preloaded()
    c = svc.client("r")
    c.read(bid, v, 0, CHUNK)                      # warm the cache
    warm = svc.page_cache.cached_page_ids()
    assert warm
    c.set_retention(bid, keep_last=1)
    c.write(bid, b"\xff" * CHUNK, 0)              # v+1 supersedes v's pages
    collect_garbage(svc)
    # v is retired: a read must answer the typed error even though its
    # pages were resident moments ago
    with pytest.raises(RetiredVersion):
        c.read(bid, v, 0, CHUNK)
    # no cached page outlives its sweep: everything still cached exists
    # on at least one provider
    stored = set()
    for p in svc.pm.all_providers():
        stored.update(p.store.iter_pids())
    assert svc.page_cache.cached_page_ids() <= stored


def test_retire_intent_evicts_before_any_delete():
    """The gc_epoch listener alone (no sweep RPC yet) must already have
    dropped the retired version's pages from the cache."""
    svc, bid, v = _preloaded()
    c = svc.client("r")
    c.read(bid, v, 0, CHUNK)
    before = svc.page_cache.cached_page_ids()
    assert before
    epoch0 = svc.vm.gc_epoch(bid)
    c.set_retention(bid, keep_last=1)
    _kept, newly = svc.vm.plan_retirement(bid, client="t")
    assert newly, "test needs at least one retired version"
    assert svc.vm.gc_epoch(bid) == epoch0 + 1
    retired_pds = {pid for vv in newly
                   for pid, *_ in svc.vm.update_log(bid, vv).pd}
    assert not (svc.page_cache.cached_page_ids() & retired_pds)


def test_delete_pages_invalidates_even_on_miss():
    svc, bid, v = _preloaded()
    c = svc.client("r")
    c.read(bid, v, 0, CHUNK)
    cached = svc.page_cache.cached_page_ids()
    assert cached
    target = sorted(cached)[0]
    # endpoint down: the delete is missed — the cache entry must go anyway
    for p in svc.pm.all_providers():
        svc.kill_provider(p.pid)
    _, _, missed = svc.pm.delete_pages([(tuple(p.pid for p in
                                               svc.pm.all_providers()), target)])
    assert missed == [target]
    assert target not in svc.page_cache.cached_page_ids()


# ---------------------------------------------------------------------------
# Determinism
# ---------------------------------------------------------------------------


def test_hot_set_scenario_replays_identically():
    a = run_scenario("hot_set", 16, seed=9, ops_per_client=3)
    b = run_scenario("hot_set", 16, seed=9, ops_per_client=3)
    assert not a.errors and not b.errors
    assert a.trace_digest == b.trace_digest
    assert a.rpc == b.rpc
    c = run_scenario("hot_set", 16, seed=10, ops_per_client=3)
    assert c.trace_digest != a.trace_digest   # seeds explore schedules


def test_hot_set_cache_cuts_data_plane_rpcs():
    cold = run_scenario("hot_set", 16, seed=9, ops_per_client=3,
                        page_cache_bytes=0)
    warm = run_scenario("hot_set", 16, seed=9, ops_per_client=3)
    assert warm.rpc["provider_read_rounds"] * 2 <= cold.rpc["provider_read_rounds"]
    assert warm.ops == cold.ops


def test_paper_scenarios_pin_cache_off():
    """The §5 reproductions model distinct nodes sharing nothing: their
    runs must not serve repeat reads from a shared in-process cache."""
    r = run_scenario("readers", 8, seed=3, ops_per_client=3)
    assert not r.errors
    assert r.rpc["page_cache_hits"] == 0
    assert r.rpc["page_cache_misses"] == 0   # cache disabled, not just cold
    # explicit override still wins
    r2 = run_scenario("readers", 8, seed=3, ops_per_client=3,
                      page_cache_bytes=64 * 1024 * 1024)
    assert not r2.errors
