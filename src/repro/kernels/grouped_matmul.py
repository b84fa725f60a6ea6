"""Grouped matrix product over the experts a chip holds (Pallas, TPU).

``grouped_matmul(lhs, rhs, group_sizes)``: the rows of ``lhs`` are sorted
by group; rows ``[start_g, start_g + group_sizes[g])`` are multiplied by
``rhs[g]``, and rows past the last group give zeros.  It is the expert
layer's SwiGLU products: the (token, choice) pairs routed to this chip's
experts, sorted by expert, times each expert's weights.

The Mosaic kernels are JAX's megablox ``gmm`` (forward, and the gradient
of ``lhs``, with ``rhs`` transposed) and ``tgmm`` (the gradient of
``rhs``).  Each runs as the root of a jitted function of its own name,
so its device op is named ``expert_gmm`` or ``expert_tgmm`` in a trace
(megablox's own jitted entry points would name it ``gmm``, or a
transpose of it).  Only the tiles of the groups present are computed:
the grid follows the group sizes, not the rows' upper bound.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# the module, not the package's custom-VJP ``gmm`` of the same name
_mb = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")

# (m, k, n) tile: 512 rows of 1024-wide operands; with double buffering
# and the f32 accumulator about 10 MiB of VMEM
TILE = (512, 1024, 1024)


def tiling(m: int, k: int, n: int):
    """The tile for an (m, k) x (k, n) product: ``TILE``, cut to the
    operands, with a row tile that divides ``m``."""
    tm = TILE[0]
    while m % tm:
        tm //= 2
    return (tm, min(TILE[1], k), min(TILE[2], n))


@functools.partial(jax.jit, static_argnames=("tile", "transpose_rhs", "interpret"))
def expert_gmm(lhs, rhs, group_sizes, tile, transpose_rhs, interpret):
    return _mb.gmm.__wrapped__(lhs, rhs, group_sizes, lhs.dtype, tile,
                               transpose_rhs=transpose_rhs, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def expert_tgmm(lhs_t, grad, group_sizes, tile, interpret):
    return _mb.tgmm.__wrapped__(lhs_t, grad, group_sizes, grad.dtype, tile,
                                interpret=interpret)


def _in_groups(out, group_sizes):
    """Rows past the last group, which the kernel leaves unwritten, as zeros."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0)
    return jnp.where(rows < jnp.sum(group_sizes), out, jnp.zeros((), out.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul_pallas(lhs, rhs, group_sizes, interpret: bool = False):
    """lhs (m, k), rhs (groups, k, n), group_sizes (groups,) int32 -> (m, n)."""
    out, _ = _fwd(lhs, rhs, group_sizes, interpret)
    return out


def _fwd(lhs, rhs, group_sizes, interpret):
    tile = tiling(lhs.shape[0], lhs.shape[1], rhs.shape[2])
    out = _in_groups(expert_gmm(lhs, rhs, group_sizes, tile=tile, transpose_rhs=False,
                                interpret=interpret), group_sizes)
    return out, (lhs, rhs, group_sizes)


def _bwd(interpret, res, grad):
    lhs, rhs, group_sizes = res
    m, k = lhs.shape
    n = rhs.shape[2]
    d_lhs = _in_groups(expert_gmm(grad, rhs, group_sizes, tile=tiling(m, n, k),
                                  transpose_rhs=True, interpret=interpret), group_sizes)
    d_rhs = expert_tgmm(lhs.swapaxes(0, 1), grad, group_sizes, tile=tiling(m, k, n),
                        interpret=interpret)
    return d_lhs, d_rhs.astype(rhs.dtype), None


grouped_matmul_pallas.defvjp(_fwd, _bwd)
