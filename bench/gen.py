"""Inputs and initial weights, made from ``--seed`` alone.

The same seed gives the same corpus and the same initial state.  The
reference builds its copy of the initial weights with these functions
too, so it takes nothing that the program under test has made.
"""

from __future__ import annotations

import zlib
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

INIT_STD = 0.02   # the configuration's initializer_range


def seed_words(seed: int) -> Tuple[int, int]:
    """Two 31-bit words of a seed of any size (seeds may exceed 32 bits)."""
    w = np.random.SeedSequence(int(seed)).generate_state(2)
    return int(w[0]) & 0x7FFFFFFF, int(w[1]) & 0x7FFFFFFF


def prng_key(seed: int) -> jax.Array:
    a, b = seed_words(seed)
    return jax.random.fold_in(jax.random.PRNGKey(a), b)


def path_str(path) -> str:
    return "/".join(str(p.key) if hasattr(p, "key") else str(p.idx) for p in path)


def leaves_with_paths(tree) -> List[Tuple[str, object]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return sorted(((path_str(p), leaf) for p, leaf in flat), key=lambda kv: kv[0])


def init_leaf(key: jax.Array, path: str, shape, dtype) -> jax.Array:
    """N(0, INIT_STD) in float32, rounded to the leaf's stored type."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(k, shape, jnp.float32) * INIT_STD).astype(dtype)


def init_params(key: jax.Array, abstract_params):
    """A parameter tree shaped like ``abstract_params``, each leaf keyed by its path."""
    return jax.tree_util.tree_map_with_path(
        lambda p, s: init_leaf(key, path_str(p), s.shape, s.dtype), abstract_params)


def init_params_by_path(key: jax.Array, shapes: Dict[str, Tuple[tuple, object]]):
    """The same leaves as :func:`init_params`, as a flat ``{path: array}``."""
    return {p: init_leaf(key, p, shape, dt) for p, (shape, dt) in shapes.items()}


def make_params(seed: int, abstract_params, shardings):
    """The stored weights, made on the device in one jitted call."""
    return jax.jit(lambda k: init_params(k, abstract_params),
                   out_shardings=shardings)(prng_key(seed))


def make_train_state(seed: int, state_abs, shardings):
    """A fresh training state: weights from the seed, an fp32 master copy
    equal to them, zero Adam moments, step 0 — one jitted call."""
    def build(key):
        params = init_params(key, state_abs["params"])
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return {
            "params": params,
            "opt": {"mu": zeros,
                    "nu": jax.tree.map(jnp.zeros_like, zeros),
                    "master": jax.tree.map(lambda p: p.astype(jnp.float32), params),
                    "count": jnp.zeros((), jnp.int32)},
            "step": jnp.zeros((), jnp.int32),
        }
    return jax.jit(build, out_shardings=shardings)(prng_key(seed))


def corpus(seed: int, n_tokens: int, vocab: int) -> np.ndarray:
    """The token stream the trainer reads: ids uniform over the vocabulary."""
    rng = np.random.default_rng(list(seed_words(seed)) + [1])
    return rng.integers(0, vocab, n_tokens, dtype=np.int32)


def batches(stream: np.ndarray, batch: int, seq: int, n: int):
    """The first ``n`` (tokens, labels) batches of one reader over ``stream``:
    consecutive windows of batch·(seq+1) tokens, labels shifted by one."""
    w = batch * (seq + 1)
    out = []
    for i in range(n):
        flat = stream[i * w:(i + 1) * w].reshape(batch, seq + 1)
        out.append((flat[:, :-1], flat[:, 1:]))
    return out
