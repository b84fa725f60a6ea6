"""Paper Fig 2(b): aggregate read bandwidth under concurrent readers.

One client appends until the blob holds ``total_mb``; then N in
{1, 25, 50, 100, 175} readers each read a disjoint chunk (the paper's
"concurrently read distinct 64 MB chunks", scaled).  Readers are driven
sequentially in wall time — the simulated wire accounts every endpoint's
busy time independently of issue order, so the derived makespan models
true concurrency (client NICs + provider contention), which is what the
paper measured.  Expect a mild per-reader decline (60 -> 49 MB/s in the
paper at 175 readers).
"""

from __future__ import annotations

from benchmarks.common import Reporter, timer
from repro.core import BlobSeerService


def run(rep: Reporter, *, total_mb: int = 128, chunk_mb: int = 8) -> None:
    n_nodes = 175
    # page cache OFF: the paper's readers run on 175 *distinct* nodes;
    # a shared in-process cache would serve the wrapped-around chunks
    # locally and fake the provider contention this figure measures.
    # The cached regime has its own benchmark (bench_cache).
    svc = BlobSeerService(n_providers=n_nodes - 2, n_meta_shards=n_nodes - 2,
                          page_cache_bytes=0)
    writer = svc.client("writer")
    bid = writer.create(psize=64 * 1024)
    payload = b"\xcd" * (4 * 1024 * 1024)
    for _ in range(total_mb // 4):
        writer.append(bid, payload)
    version = writer.get_recent(bid)
    size = writer.get_size(bid, version)

    for n_readers in (1, 25, 50, 100, 175):
        svc.reset_rpc_counters()
        chunk = chunk_mb * 1024 * 1024
        t0 = timer()
        for r in range(n_readers):
            c = svc.client(f"reader-{r}")
            # distinct chunks while they last, then strided overlap — at
            # 128 pages/chunk over 173 providers the page->provider
            # collisions are what bound aggregate bandwidth (paper Fig 2b)
            off = (r * chunk) % (size - chunk)
            c.read(bid, version, off, chunk)
        wall = timer() - t0
        makespan = svc.wire.sim_span()
        total_bytes = n_readers * chunk
        agg = total_bytes / max(makespan, 1e-9) / 1e6
        per = agg / n_readers
        rpc = svc.rpc_report()
        rep.add(
            f"read_concurrent_n{n_readers}",
            wall / n_readers * 1e6,
            f"sim_per_reader={per:.1f}MBps sim_aggregate={agg:.1f}MBps "
            f"chunk={chunk_mb}MB "
            f"rpcs_per_reader={rpc['wire_round_trips'] / n_readers:.1f} "
            f"pages_per_reader={rpc['provider_read_pages'] / n_readers:.1f}",
        )
