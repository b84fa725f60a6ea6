"""Pallas TPU kernel: per-page polynomial digest.

The checkpoint layer fingerprints every page of device-resident training
state to detect copy-on-write deltas (only changed pages are re-written
to BlobSeer providers), and the same fingerprints feed the dedup
handshake: ``blobckpt`` passes them to ``BlobClient.write_many`` so the
content-hash index can match equal pages without re-hashing.  At
multi-TB state sizes this scan must run at HBM bandwidth on the chip,
not on the host — hence a TPU kernel.  Off-TPU callers with plain bytes
use the numpy twin ``hostdigest.host_page_digest`` (same constants,
same padding, bit-identical results).

Math (same as ``ref.ref_page_digest``): for each page ``p`` and each of
two independent odd multipliers ``A_m``::

    digest[p, m] = sum_i (x[p, i] + SALT) * A_m^(W-1-i)   (mod 2^32)

evaluated blockwise Horner-style over word-blocks of size ``block_w``::

    acc <- acc * A_m^block_w + poly_block(acc_block)

TPU adaptation notes:

* uint32 VPU arithmetic wraps mod 2^32 natively — no emulation needed;
  only the row reduction runs on the int32 bitcast, since Mosaic does
  not reduce over unsigned integers;
* pages tile the sublane axis (8) and words the lane axis (128), so a
  (page_tile, block_w) = (8, 512) block is four perfectly aligned
  (8, 128) vregs;
* the word-block axis is the innermost (sequential) grid dimension; the
  running accumulator lives in VMEM scratch and is multiplied by the
  per-block constant ``A^block_w`` each step — a classic reduction
  pipeline, bandwidth-bound by design (arithmetic intensity ~2 flops
  per 4 bytes).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import DIGEST_MULTS, DIGEST_SALT, digest_weights

U32 = jnp.uint32


def _block_mults(block_w: int) -> tuple[int, int]:
    """``A_m^block_w mod 2^32`` for both multipliers."""
    out = []
    for mult in DIGEST_MULTS:
        acc = 1
        for _ in range(block_w):
            acc = (acc * mult) & 0xFFFFFFFF
        out.append(acc)
    return tuple(out)


def _wrapping_row_sum(x: jax.Array) -> jax.Array:
    """Row sums of a u32 block mod 2^32.

    Mosaic has no reduction over unsigned integers, so the sum runs on
    the int32 bitcast: two's-complement addition wraps to the same bits.
    """
    s = jax.lax.bitcast_convert_type(x, jnp.int32).sum(axis=1, dtype=jnp.int32)
    return jax.lax.bitcast_convert_type(s, U32)


def _digest_kernel(x_ref, w_ref, o_ref, acc_ref, *, block_mults):
    """Grid: (page_tiles, word_blocks); word_blocks is sequential."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...] + U32(DIGEST_SALT)          # (PT, BW)
    w = w_ref[...]                              # (2, BW)
    # poly over this block for both multipliers: (PT, 2)
    poly0 = _wrapping_row_sum(x * w[0][None, :])
    poly1 = _wrapping_row_sum(x * w[1][None, :])
    carry0 = acc_ref[:, 0] * U32(block_mults[0]) + poly0
    carry1 = acc_ref[:, 1] * U32(block_mults[1]) + poly1
    acc_ref[...] = jnp.stack([carry0, carry1], axis=1)

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("page_tile", "block_w", "interpret"))
def page_digest_pallas(
    pages_u32: jax.Array,
    *,
    page_tile: int = 8,
    block_w: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """(n_pages, n_words) u32 -> (n_pages, 2) u32 digests via Pallas."""
    n_pages, n_words = pages_u32.shape
    pad_p = (-n_pages) % page_tile
    pad_w = (-n_words) % block_w
    if pad_p or pad_w:
        pages_u32 = jnp.pad(pages_u32, ((0, pad_p), (0, pad_w)))
    P, W = pages_u32.shape
    # Per-block polynomial weights are identical for every block
    # (A^(BW-1-i)); the cross-block shift is the scalar A^BW in scratch.
    w_block = jnp.asarray(digest_weights(block_w))  # (2, BW)
    grid = (P // page_tile, W // block_w)
    out = pl.pallas_call(
        functools.partial(_digest_kernel, block_mults=_block_mults(block_w)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((page_tile, block_w), lambda i, j: (i, j)),
            pl.BlockSpec((2, block_w), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((page_tile, 2), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((P, 2), U32),
        scratch_shapes=[pltpu.VMEM((page_tile, 2), U32)],
        interpret=interpret,
    )(pages_u32, w_block)
    return out[:n_pages]
