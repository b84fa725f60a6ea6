"""The batched metadata/data plane.

Covers the read-side batching refactor: ``MetadataDHT.get_many``,
level-synchronous READ_META, shared batched border descents,
``ProviderManager.fetch_pages`` grouping, the client node cache's LRU
bound — and the failure-injection semantics (a downed shard/provider
mid-batch falls over to replicas exactly like the single-get paths).
"""

import random

import pytest

from repro.core import BlobSeerService, EndpointDown
from repro.core import segment_tree as st
from repro.core.cache import NodeCache
from repro.core.dht import MetadataDHT
from repro.core.transport import Wire


# ---------------------------------------------------------------------------
# MetadataDHT.get_many
# ---------------------------------------------------------------------------


def _fill(dht, n=40):
    items = [(("blob", 1, i, 1), {"node": i}) for i in range(n)]
    dht.put_many(items, peer="c")
    return items


def test_get_many_matches_single_gets():
    dht = MetadataDHT(Wire(), 8)
    items = _fill(dht)
    keys = [k for k, _ in items] + [("blob", 9, 0, 1)]  # one absent key
    got = dht.get_many(keys, peer="c")
    for key in keys:
        assert got[key] == dht.get(key, peer="c")
    assert got[("blob", 9, 0, 1)] is None


def test_get_many_batches_per_shard():
    dht = MetadataDHT(Wire(), 4)
    items = _fill(dht)
    dht.reset_rpc_counters()
    dht.get_many([k for k, _ in items])
    ctr = dht.rpc_counters()
    assert ctr["get_keys"] == len(items)
    assert ctr["get_rounds"] == 1            # one batched wave
    assert ctr["get_shard_rpcs"] <= 4        # at most one RPC per shard


def test_get_many_fails_over_to_replicas_mid_batch():
    wire = Wire()
    # 10 shards, 40 keys: the keys disqualify at most 40 of the 45
    # shard pairs, so a pair that never co-owns a key always exists
    dht = MetadataDHT(wire, 10, replication=2)
    items = _fill(dht)
    # down two shards that never co-own a key, so every key keeps a
    # live replica (the pair depends on the ring layout, so compute it)
    import itertools
    owner_sets = [
        frozenset(s.shard_id for s in dht._home_shards(k)) for k, _ in items]
    for a, b in itertools.combinations(dht.shards, 2):
        if frozenset((a.shard_id, b.shard_id)) not in owner_sets:
            wire.set_down(a.shard_id, True)
            wire.set_down(b.shard_id, True)
            break
    got = dht.get_many([k for k, _ in items])
    assert got == {k: v for k, v in items}


def test_get_many_raises_when_all_replicas_down():
    wire = Wire()
    dht = MetadataDHT(wire, 3, replication=1)
    items = _fill(dht)
    for i in range(3):
        wire.set_down(f"meta-{i:04d}", True)
    with pytest.raises(EndpointDown):
        dht.get_many([items[0][0]])


def test_get_replica_hole_falls_through():
    """A partial put (one replica down at write time) leaves a hole; a
    later get that races to the holey replica must keep looking."""
    wire = Wire()
    dht = MetadataDHT(wire, 4, replication=2)
    key = ("blob", 7, 3, 1)
    primary, backup = dht._home_shards(key)
    wire.set_down(primary.shard_id, True)
    dht.put(key, {"v": 7})                 # lands only on the backup
    wire.set_down(primary.shard_id, False)
    # force the racing order to try the holey primary first
    wire.stats(backup.shard_id).sim_busy_until = 1e9
    assert dht.get(key) == {"v": 7}
    assert dht.get_many([key])[key] == {"v": 7}


# ---------------------------------------------------------------------------
# Level-synchronous READ_META + batched border descents
# ---------------------------------------------------------------------------


def test_read_meta_round_trips_bounded_by_depth():
    svc = BlobSeerService(n_providers=4, n_meta_shards=16)
    c = svc.client()
    bid = c.create(psize=64)
    c.append(bid, b"x" * 64 * 1024)        # 1024 pages -> depth 10
    v = c.get_recent(bid)
    root = svc.vm.root_pages_published(bid, v)
    svc.dht.reset_rpc_counters()
    pd = st.read_meta(svc.dht, c._owner_fn(bid), v, root, 100, 164)
    ctr = svc.dht.rpc_counters()
    assert len(pd) == 64
    assert ctr["get_rounds"] <= root.bit_length()          # <= depth + 1
    assert ctr["get_keys"] >= 5 * ctr["get_rounds"]        # >=5x vs per-node


def test_read_meta_against_plain_dict_fallback():
    """read_meta accepts any store with get(); the batched path must
    degrade gracefully when get_many is absent."""

    class DictStore(dict):
        def get(self, key, peer=None):
            return dict.get(self, key)

    svc = BlobSeerService(n_providers=4, n_meta_shards=2)
    c = svc.client()
    bid = c.create(psize=16)
    c.write(bid, bytes(range(256)), 0)
    v = c.get_recent(bid)
    root = svc.vm.root_pages_published(bid, v)
    mirror = DictStore()
    for shard in svc.dht.shards:
        mirror.update(shard._kv)
    pd = st.read_meta(mirror, c._owner_fn(bid), v, root, 0, 16)
    assert [d.page_index for d in pd] == list(range(16))


def test_batched_border_resolution_preserves_versioning():
    """Random writes/appends: every snapshot stays byte-identical to a
    flat oracle (build_meta now resolves borders level-batched)."""
    svc = BlobSeerService(n_providers=4, n_meta_shards=4)
    c = svc.client()
    bid = c.create(psize=16)
    rnd = random.Random(7)
    versions = {0: b""}
    cur = b""
    for _ in range(25):
        data = bytes([rnd.randrange(256)]) * rnd.randrange(1, 70)
        if not cur or rnd.random() < 0.5:
            c.append(bid, data)
            cur = cur + data
        else:
            off = rnd.randrange(0, len(cur))
            c.write(bid, data, off)
            buf = bytearray(cur)
            buf[off : off + len(data)] = data
            cur = bytes(buf)
        versions[max(versions) + 1] = cur
    for v, want in versions.items():
        if v == 0:
            continue
        assert c.read(bid, v, 0, len(want)) == want
        # a cold client (no node cache) agrees
    cold = svc.client()
    top = max(versions)
    assert cold.read(bid, top, 0, len(versions[top])) == versions[top]


# ---------------------------------------------------------------------------
# ProviderManager.fetch_pages
# ---------------------------------------------------------------------------


def test_fetch_pages_matches_fetch_page():
    svc = BlobSeerService(n_providers=4, n_meta_shards=2)
    c = svc.client()
    bid = c.create(psize=32)
    v = c.write(bid, bytes(range(128)), 0)
    pd = st.read_meta(svc.dht, c._owner_fn(bid), v,
                      svc.vm.root_pages_published(bid, v), 0, 4)
    reqs = [(d.providers, d.page_id, 1, 7) for d in pd]
    batched = svc.pm.fetch_pages(reqs)
    singles = [svc.pm.fetch_page(d.providers, d.page_id, 1, 7) for d in pd]
    assert batched == singles


def test_fetch_pages_groups_per_provider():
    svc = BlobSeerService(n_providers=2, n_meta_shards=2)
    c = svc.client()
    bid = c.create(psize=32)
    v = c.write(bid, b"p" * 32 * 8, 0)     # 8 pages over 2 providers
    pd = st.read_meta(svc.dht, c._owner_fn(bid), v,
                      svc.vm.root_pages_published(bid, v), 0, 8)
    before = svc.wire.total_round_trips()
    svc.pm.fetch_pages([(d.providers, d.page_id, 0, None) for d in pd])
    # 8 pages on 2 endpoints -> 2 batched round trips, not 8
    assert svc.wire.total_round_trips() - before == 2


def test_fetch_pages_fails_over_mid_batch():
    svc = BlobSeerService(n_providers=4, n_meta_shards=2, data_replication=2)
    c = svc.client()
    bid = c.create(psize=64)
    payload = bytes(range(256)) * 16
    v = c.write(bid, payload, 0)
    svc.kill_provider("prov-0001")
    pd = st.read_meta(svc.dht, c._owner_fn(bid), v,
                      svc.vm.root_pages_published(bid, v), 0, 64)
    chunks = svc.pm.fetch_pages([(d.providers, d.page_id, 0, None) for d in pd])
    assert b"".join(chunks) == payload
    # and the client read path agrees end-to-end
    assert c.read(bid, v, 0, len(payload)) == payload


def test_fetch_pages_raises_after_all_replicas_down():
    svc = BlobSeerService(n_providers=2, n_meta_shards=2, data_replication=1)
    c = svc.client()
    bid = c.create(psize=64)
    v = c.write(bid, b"z" * 1024, 0)
    pd = st.read_meta(svc.dht, c._owner_fn(bid), v,
                      svc.vm.root_pages_published(bid, v), 0, 16)
    svc.kill_provider("prov-0000")
    svc.kill_provider("prov-0001")
    with pytest.raises(EndpointDown):
        svc.pm.fetch_pages([(d.providers, d.page_id, 0, None) for d in pd])


# ---------------------------------------------------------------------------
# NodeCache: batch-aware LRU
# ---------------------------------------------------------------------------


def test_node_cache_lru_is_bounded_and_evicts_oldest(monkeypatch):
    dht = MetadataDHT(Wire(), 2)
    cache = NodeCache(dht)
    monkeypatch.setattr(NodeCache, "MAX_ENTRIES", 4)
    for i in range(6):
        cache.put(("k", i), {"v": i})
    assert len(cache._cache) == 4           # bounded, no clear-all
    assert ("k", 0) not in cache._cache and ("k", 1) not in cache._cache
    assert cache.get(("k", 5)) == {"v": 5}  # newest still resident

    # touching an entry protects it from eviction (true LRU order)
    cache.get(("k", 2))
    cache.put(("k", 6), {"v": 6})
    assert ("k", 2) in cache._cache
    assert ("k", 3) not in cache._cache


def test_node_cache_get_many_serves_hits_locally():
    dht = MetadataDHT(Wire(), 4)
    items = _fill(dht, 10)
    cache = NodeCache(dht)
    keys = [k for k, _ in items]
    first = cache.get_many(keys)
    assert first == {k: v for k, v in items}
    assert cache.misses == 10
    dht.reset_rpc_counters()
    second = cache.get_many(keys)
    assert second == first
    assert cache.hits == 10
    assert dht.rpc_counters()["get_keys"] == 0   # pure local hits


def test_read_after_cache_eviction_still_correct(monkeypatch):
    monkeypatch.setattr(NodeCache, "MAX_ENTRIES", 8)
    svc = BlobSeerService(n_providers=4, n_meta_shards=2)
    c = svc.client()
    bid = c.create(psize=16)
    payload = bytes(range(256)) * 4
    v = c.write(bid, payload, 0)           # 64 pages >> 8 cache slots
    assert c.read(bid, v, 0, len(payload)) == payload
    assert c.read(bid, v, 100, 500) == payload[100:600]
