"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run append read # subset

Emits ``name,us_per_call,derived`` CSV rows.
"""

from __future__ import annotations

import sys

from benchmarks.common import Reporter

BENCHES = ["append", "read", "meta", "space", "gc", "cache",
           "failover", "durability", "watch", "ring", "concurrency", "e2e"]


def main() -> None:
    which = sys.argv[1:] or BENCHES
    rep = Reporter()
    print("name,us_per_call,derived")
    for name in which:
        if name == "append":
            from benchmarks import bench_append as m
        elif name == "read":
            from benchmarks import bench_read as m
        elif name == "meta":
            from benchmarks import bench_meta as m
        elif name == "space":
            from benchmarks import bench_space as m
        elif name == "gc":
            from benchmarks import bench_gc as m
        elif name == "cache":
            from benchmarks import bench_cache as m
        elif name == "failover":
            from benchmarks import bench_failover as m
        elif name == "durability":
            from benchmarks import bench_durability as m
        elif name == "watch":
            from benchmarks import bench_watch as m
        elif name == "ring":
            from benchmarks import bench_ring as m
        elif name == "concurrency":
            from benchmarks import bench_concurrency as m
        elif name == "e2e":
            from benchmarks import bench_e2e as m
        else:
            raise SystemExit(f"unknown bench {name!r}; known: {BENCHES}")
        m.run(rep)


if __name__ == "__main__":
    main()
