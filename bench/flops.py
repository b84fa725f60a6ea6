"""Bytes of the measured work, computed from shapes alone.

A model's operations are its family's (``bench.families``)."""

from __future__ import annotations

from typing import Iterable

DIGEST_BLOCK_WORDS = 512   # each page's u32 words are padded to a multiple of this
DIGEST_OUT_BYTES = 8       # two u32 fingerprints per page


def digest_pages(nbytes: int, page_bytes: int) -> int:
    return -(-max(nbytes, 1) // page_bytes)


def digest_bytes(leaf_nbytes: Iterable[int], page_bytes: int) -> int:
    """HBM bytes the page-digest kernel moves over leaves of these sizes.

    It reads every page's u32 words, padded to ``DIGEST_BLOCK_WORDS``, and
    writes two u32 per page.
    """
    words = page_bytes // 4
    words += (-words) % DIGEST_BLOCK_WORDS
    return sum(digest_pages(n, page_bytes) * (4 * words + DIGEST_OUT_BYTES)
               for n in leaf_nbytes)
