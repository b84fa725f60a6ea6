"""Operations and bytes of the measured work, computed from shapes alone."""

from __future__ import annotations

from typing import Iterable

DIGEST_BLOCK_WORDS = 512   # each page's u32 words are padded to a multiple of this
DIGEST_OUT_BYTES = 8       # two u32 fingerprints per page


def n_params(cfg: dict) -> int:
    """Parameters of a decoder of ``cfg`` (the configuration file's keys)."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    attn = d * h * dh + 2 * d * hkv * dh + h * dh * d
    mlp = 3 * d * f if cfg["hidden_act"] == "silu" else 2 * d * f
    head = 0 if cfg["tie_word_embeddings"] else d * v
    return cfg["num_hidden_layers"] * (attn + mlp) + v * d + head


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs of one trained token: 6·N plus attention, 12·L·d·T.

    N counts the embedding table, which the tied output head multiplies;
    the attention term counts the full T×T score and value products of the
    forward and backward passes, causal mask or not (the usual MFU rule).
    Recomputation does not count.
    """
    d = cfg["num_attention_heads"] * (cfg["hidden_size"] // cfg["num_attention_heads"])
    return 6.0 * n_params(cfg) + 12.0 * cfg["num_hidden_layers"] * d * seq


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
