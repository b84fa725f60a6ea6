"""Named phase spans of the program, on the host clock and in the profiler's trace.

``span(name)`` times one phase of a save, of the store's write path or of
a GC round.  It is always on: each span appends one record
``(id, parent_id, name, t0, t1)`` to a bounded ring, with ``t0``/``t1``
from ``time.perf_counter()`` and ``parent_id`` the innermost span open on
the same thread (``None`` at the top).  Where JAX is loaded, the span
also opens ``jax.profiler.TraceAnnotation(name)``, so a profiler trace
shows the same names on the device trace's clock.  A process that never
imported JAX cannot be under its profiler, and the storage core does not
import it for this.

Spans stop at the phase, or the phase per leaf: none is opened per page,
per tree node or per RPC (``BlobSeerService.rpc_report()`` counts those).
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import sys
import threading
import time
from typing import Deque, Iterator, List, Optional, Tuple

# every name a span may take, and what it covers
SPAN_NAMES = (
    "ckpt.save",         # all of BlobCheckpointer.save
    "ckpt.digest",       # per leaf: page digests, delta mask, their read-back
    "ckpt.d2h",          # per dirty leaf: the device-to-host copy
    "ckpt.pack",         # per dirty leaf: cutting it into page-aligned runs
    "ckpt.commit",       # manifest, commit pointer, pin
    "blob.store_pages",  # BlobClient._update_many: storing the full pages
    "blob.publish",      # _update_many: versions, boundary pages, metadata, completion
    "gc.round",          # all of collect_garbage
    "gc.mark",           # mark_live
    "gc.sweep",          # _sweep and the orphan pass
)
_LEGAL = frozenset(SPAN_NAMES)

RING_SIZE = 1 << 16

# (id, parent_id, name, t0, t1)
Record = Tuple[int, Optional[int], str, float, float]

_ring: Deque[Record] = collections.deque(maxlen=RING_SIZE)
_ids = itertools.count(1)
_local = threading.local()


def _annotation(name: str):
    profiler = sys.modules.get("jax.profiler")
    return profiler.TraceAnnotation(name) if profiler else contextlib.nullcontext()


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Time the enclosed phase as a span ``name`` of ``SPAN_NAMES``."""
    if name not in _LEGAL:
        raise ValueError(f"unknown span {name!r}; known: {SPAN_NAMES}")
    stack: List[int] = _local.__dict__.setdefault("stack", [])
    sid = next(_ids)
    parent = stack[-1] if stack else None
    stack.append(sid)
    t0 = time.perf_counter()
    try:
        with _annotation(name):
            yield
    finally:
        t1 = time.perf_counter()
        stack.pop()
        _ring.append((sid, parent, name, t0, t1))


def recorded() -> List[Record]:
    """The ring's records, oldest first (a span is recorded when it closes)."""
    return list(_ring)
