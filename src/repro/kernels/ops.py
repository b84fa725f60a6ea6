"""Public kernel API with backend dispatch.

Callers use these wrappers, never the kernels directly:

* on a TPU the Pallas kernels always run compiled;
* elsewhere the pure-jnp references run under jit, unless
  ``REPRO_PALLAS=interpret`` sends the Pallas kernels through the
  interpreter (the kernel-vs-oracle test path).  It is the variable's
  only value, and a TPU ignores it.

Every wrapper normalizes shapes/dtypes so the Pallas and reference paths
see bit-identical inputs — the correctness contract the tests assert.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import ref as _ref
from repro.kernels.delta_mask import delta_mask_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.grouped_matmul import grouped_matmul_pallas
from repro.kernels.linear_scan import linear_scan_pallas
from repro.kernels.page_digest import page_digest_pallas

DIGEST_BLOCK_WORDS = 512


def _backend() -> str:
    return jax.default_backend()


def _interpret() -> bool:
    if _backend() == "tpu":
        return False
    mode = os.environ.get("REPRO_PALLAS", "")
    if mode not in ("", "interpret"):
        raise ValueError(f"REPRO_PALLAS={mode!r}: the only value is 'interpret'")
    return mode == "interpret"


def use_pallas() -> bool:
    return _backend() == "tpu" or _interpret()


# ---------------------------------------------------------------------------
# digest / delta
# ---------------------------------------------------------------------------


def as_page_words(data: jax.Array, page_bytes: int) -> jax.Array:
    """Reinterpret an array's bytes as (n_pages, words) u32, zero-padded.

    The canonical digest domain: bytes are padded to a whole number of
    ``page_bytes`` pages and each page to a multiple of
    ``DIGEST_BLOCK_WORDS`` 32-bit words, identically for both backends.
    Words are built from the element type directly, so a device-resident
    leaf is fingerprinted in place.  Narrow elements are packed by
    strided lane slices of each page: a trailing axis of 2 or 4 would be
    padded to a full 128-lane tile on a TPU.
    """
    flat = data.reshape(-1)
    itemsize = flat.dtype.itemsize
    assert page_bytes % max(itemsize, 4) == 0
    pad = (-flat.shape[0] * itemsize) % page_bytes // itemsize
    if pad:
        flat = jnp.pad(flat, (0, pad))
    if itemsize >= 4:
        words = jax.lax.bitcast_convert_type(flat, jnp.uint32)
    else:
        per = 4 // itemsize
        bits = jax.lax.bitcast_convert_type(flat, jnp.dtype(f"uint{8 * itemsize}"))
        bits = bits.reshape(-1, page_bytes // itemsize)
        words = bits[:, 0::per].astype(jnp.uint32)
        for k in range(1, per):
            words |= bits[:, k::per].astype(jnp.uint32) << (8 * itemsize * k)
    words = words.reshape(-1, page_bytes // 4)
    word_pad = (-words.shape[1]) % DIGEST_BLOCK_WORDS
    if word_pad:
        words = jnp.pad(words, ((0, 0), (0, word_pad)))
    return words


def _device_operand(data):
    """Host arrays go to the device as raw bytes, whatever their dtype.

    ``jnp.asarray`` would narrow a 64-bit host array to 32 bits, and the
    digest must cover the bytes the checkpoint writes.
    """
    if isinstance(data, (np.ndarray, np.generic)):
        return np.ascontiguousarray(data).reshape(-1).view(np.uint8)
    return data


def _mesh_of(x) -> Optional[Mesh]:
    """A 1-D mesh over the devices of a multi-device array, in mesh order."""
    sharding = getattr(x, "sharding", None)
    if sharding is None or len(sharding.device_set) == 1:
        return None
    if not isinstance(sharding, NamedSharding):
        raise TypeError(f"cannot split pages over a {type(sharding).__name__}")
    return Mesh(sharding.mesh.devices.reshape(-1), ("pages",))


def _over_pages(kernel, mesh: Optional[Mesh], *arrays: jax.Array) -> jax.Array:
    """Run a per-page kernel, its pages split over every device of ``mesh``.

    A Mosaic kernel cannot be partitioned by the compiler, so on a mesh
    each device runs it over its own contiguous share of the pages; the
    (small) per-page result comes back replicated on the mesh.
    """
    if mesh is None:
        return kernel(*arrays)
    n = arrays[0].shape[0]
    pad = (-n) % mesh.size
    arrays = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) for a in arrays]
    spec = P("pages")
    out = shard_map(kernel, mesh=mesh, in_specs=(spec,) * len(arrays),
                    out_specs=spec, check_vma=False)(*arrays)
    return jax.lax.with_sharding_constraint(out[:n], NamedSharding(mesh, P()))


@functools.partial(jax.jit, static_argnames=("page_bytes",))
def _page_digest_ref(data: jax.Array, page_bytes: int) -> jax.Array:
    return _ref.ref_page_digest(as_page_words(data, page_bytes))


@functools.partial(jax.jit, static_argnames=("page_bytes", "interpret", "mesh"))
def _page_digest_kernel(data, page_bytes, interpret, mesh):
    kernel = functools.partial(page_digest_pallas, block_w=DIGEST_BLOCK_WORDS,
                               interpret=interpret)
    return _over_pages(kernel, mesh, as_page_words(data, page_bytes))


def page_digest(data: jax.Array, page_bytes: int = 64 * 1024) -> jax.Array:
    """Digest device-resident data as (n_pages, 2) u32 fingerprints."""
    if use_pallas():
        return _page_digest_kernel(_device_operand(data), page_bytes,
                                   _interpret(), _mesh_of(data))
    return _page_digest_ref(_device_operand(data), page_bytes)


@jax.jit
def _delta_mask_ref(new_digest: jax.Array, old_digest: jax.Array) -> jax.Array:
    return _ref.ref_delta_mask(new_digest, old_digest)


@functools.partial(jax.jit, static_argnames=("interpret", "mesh"))
def _delta_mask_kernel(new_digest, old_digest, interpret, mesh):
    kernel = functools.partial(delta_mask_pallas, interpret=interpret)
    return _over_pages(kernel, mesh, new_digest, old_digest) != 0


def delta_mask(new_digest: jax.Array, old_digest: jax.Array) -> jax.Array:
    """(n,) bool — pages whose digest changed since the last checkpoint."""
    if use_pallas():
        return _delta_mask_kernel(new_digest, old_digest, _interpret(),
                                  _mesh_of(new_digest))
    return _delta_mask_ref(new_digest, old_digest)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "q_offset", "softcap")
)
def _attention_ref(q, k, v, causal, window, q_offset, softcap):
    return _ref.ref_attention(
        q, k, v, causal=causal, window=window, q_offset=q_offset, softcap=softcap
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    softcap: float | None = None,
) -> jax.Array:
    """GQA attention; Pallas on TPU, reference elsewhere (differentiable)."""
    if use_pallas():
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            softcap=softcap, interpret=_interpret(),
        )
    return _attention_ref(q, k, v, causal, window, q_offset, softcap)


# ---------------------------------------------------------------------------
# linear scan
# ---------------------------------------------------------------------------


@jax.jit
def _linear_scan_ref(a, x):
    return _ref.ref_linear_scan(a, x)


def linear_scan(a: jax.Array, x: jax.Array) -> jax.Array:
    """h_t = a_t * h_{t-1} + x_t over (B, T, D)."""
    if use_pallas():
        return linear_scan_pallas(a, x, interpret=_interpret())
    return _linear_scan_ref(a, x)


# ---------------------------------------------------------------------------
# grouped matmul (the expert layer)
# ---------------------------------------------------------------------------


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """lhs (m, k) rows sorted by group, rhs (groups, k, n) -> (m, n): group g's
    rows times ``rhs[g]``, zeros past the last group.  Differentiable."""
    group_sizes = group_sizes.astype(jnp.int32)
    if use_pallas():
        return grouped_matmul_pallas(lhs, rhs, group_sizes, _interpret())
    return _ref.ref_grouped_matmul(lhs, rhs, group_sizes)
