"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``ref_*`` function is the semantic ground truth: kernels are tested
against these over shape/dtype sweeps (see ``tests/test_kernels.py``),
and they double as the CPU execution path in ``ops.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Digest constants and host weight table live in ``hostdigest`` (numpy-only,
# shared with the dedup handshake); re-exported here for the kernels.
from repro.kernels.hostdigest import (  # noqa: F401  (re-export)
    DIGEST_MULTS,
    DIGEST_SALT,
    digest_weights,
)

U32 = jnp.uint32


def ref_page_digest(pages_u32: jax.Array) -> jax.Array:
    """Per-page polynomial digest.

    ``pages_u32``: (n_pages, n_words) uint32.  Returns (n_pages, 2) u32:
    ``digest[p, m] = sum_i (x[p,i] + SALT) * A_m^(n_words-1-i) mod 2^32``.
    Order-sensitive (polynomial in A), so page content permutations
    change the digest; two independent moduli give a 64-bit fingerprint
    for copy-on-write delta detection in the checkpoint layer.
    """
    n_words = pages_u32.shape[-1]
    w = jnp.asarray(digest_weights(n_words))  # (2, W)
    x = pages_u32.astype(U32) + U32(DIGEST_SALT)
    # u32 multiply-accumulate wraps mod 2^32 exactly like the kernel
    return (x[:, None, :] * w[None, :, :]).sum(axis=-1, dtype=U32)


def ref_delta_mask(new_digest: jax.Array, old_digest: jax.Array) -> jax.Array:
    """(n_pages,) bool — True where the page content changed."""
    return jnp.any(new_digest != old_digest, axis=-1)


def ref_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    softcap: float | None = None,
) -> jax.Array:
    """Reference GQA attention.

    q: (B, Hq, Tq, D);  k, v: (B, Hkv, Tk, D);  Hq % Hkv == 0.
    ``q_offset``: absolute position of q[...,-Tq,:] start (decode: Tk-1).
    ``window``: sliding-window size (key positions > window behind the
    query are masked), per Mistral/RecurrentGemma local attention.
    """
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    group = Hq // Hkv
    qf = q.astype(jnp.float32) * (D ** -0.5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qg = qf.reshape(B, Hkv, group, Tq, D)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kf)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = q_offset + jnp.arange(Tq)[:, None]
    kpos = jnp.arange(Tk)[None, :]
    mask = jnp.ones((Tq, Tk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
    return out.reshape(B, Hq, Tq, D).astype(q.dtype)


def ref_linear_scan(a: jax.Array, x: jax.Array, h0: jax.Array | None = None) -> jax.Array:
    """Diagonal linear recurrence ``h_t = a_t * h_{t-1} + x_t``.

    a, x: (B, T, D).  Returns h: (B, T, D).  This is the core of the
    RG-LRU (Griffin) and diagonal-state xLSTM paths.  Implemented with
    an associative scan (Blelloch), the standard JAX formulation.
    """
    if h0 is not None:
        x = x.at[:, 0].add(a[:, 0] * h0)

    def combine(c1, c2):
        a1, x1 = c1
        a2, x2 = c2
        return a1 * a2, a2 * x1 + x2

    _, h = jax.lax.associative_scan(combine, (a, x), axis=1)
    return h


def ref_grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """lhs (m, k) rows sorted by group, rhs (groups, k, n) -> (m, n): rows
    ``[start_g, start_g + group_sizes[g])`` times ``rhs[g]``, zeros past the
    last group (``lax.ragged_dot``, in ``lhs``'s dtype)."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype)
