"""Hand-rolled collectives: gradient compression + overlap helpers.

Int8-compressed gradient all-reduce (1-bit-Adam-family trick, stochastic
rounding): inside ``shard_map`` over the DP axis each shard quantizes to
int8 against a globally agreed scale (one cheap f32 ``pmax`` for the
scale, then the payload moves at 1/4 the bytes of bf16).  Used by the
e2e trainer's ``--grad-compress int8`` flag; the pjit path leaves
reduction to GSPMD (already bf16) — measured deltas live in
EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def _stochastic_round_int8(x: jax.Array, scale: jax.Array, rng: jax.Array) -> jax.Array:
    y = x / scale * 127.0
    lo = jnp.floor(y)
    frac = y - lo
    bern = jax.random.uniform(rng, y.shape) < frac
    return jnp.clip(lo + bern, -127, 127).astype(jnp.int8)


def int8_allreduce_mean(x: jax.Array, rng: jax.Array, *, axis_name: str) -> jax.Array:
    """All-reduce-mean of ``x`` over ``axis_name`` with int8 payload."""
    n = jax.lax.psum(1, axis_name)
    # shared scale so every shard quantizes against the same grid
    scale = jax.lax.pmax(jnp.max(jnp.abs(x)), axis_name) + 1e-12
    q = _stochastic_round_int8(x.astype(jnp.float32), scale, rng)
    s = jax.lax.psum(q.astype(jnp.int32), axis_name)
    return (s.astype(jnp.float32) * scale / 127.0 / n).astype(x.dtype)


def compressed_grad_mean(grads: Any, mesh: Mesh, axis_name: str, rng: jax.Array) -> Any:
    """Tree-wide int8 all-reduce-mean over one mesh axis via shard_map.

    Gradients are assumed replicated along every *other* mesh axis
    (host-level DP use case in examples/train_e2e.py).
    """
    leaves, treedef = jax.tree.flatten(grads)
    rngs = jax.random.split(rng, len(leaves))

    out = []
    for leaf, r in zip(leaves, rngs):
        fn = shard_map(
            functools.partial(int8_allreduce_mean, axis_name=axis_name),
            mesh=mesh,
            in_specs=(P(axis_name), P()),
            out_specs=P(axis_name),
        )
        padded = leaf.reshape(-1)
        n_dev = mesh.shape[axis_name]
        pad = (-padded.shape[0]) % n_dev
        if pad:
            padded = jnp.pad(padded, (0, pad))
        red = fn(padded, r)
        out.append(red[: leaf.size].reshape(leaf.shape).astype(leaf.dtype))
    return jax.tree.unflatten(treedef, out)
