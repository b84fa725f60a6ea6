"""Plain references: the training step and the page digest.

Nothing here imports the program.  The training reference is the
architecture's loss (its family module, ``bench.families``) and the AdamW
update written out in ``jax.numpy`` at float32 with ``highest`` matmul
precision, from the configuration file alone.  AdamW is as the file's
``optimizer`` states: global-norm clipping, bias-corrected moments,
decoupled weight decay on every leaf, linear warmup then cosine decay to
``min_lr_frac``.

``precision="fp8"`` is the control: every matmul operand rounded to
float8 e4m3 with one scale per tensor, the step below the bfloat16 that the
configuration states; gradients flow straight through the rounding.  ``fault`` plants a fault of the timed path in the
reference put in its place: ``"half_batch"`` (the loss over half the rows),
``"token"`` (one input token altered) or ``"unchanged"`` (a step that
returns its state unchanged).

The page digest is BlobSeer's fingerprint of a 16 KiB page, recomputed on
the host from the bytes read back.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from bench import gen
from bench.families import family

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _q(x, precision: str):
    """A matmul operand as ``precision`` holds it.  For float8 the backward
    pass goes straight through, in float32, at the rounded values."""
    if precision == "f32":
        return x
    scale = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX)
    q = (x / scale).astype(FP8).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"),
                   donate_argnums=(0, 1, 2))
def _adam_step(master, mu, nu, t, tokens, labels, cfg_items, precision):
    cfg = dict(cfg_items)
    opt = dict(cfg["optimizer"])
    loss, g = jax.value_and_grad(family(cfg).loss_fn)(
        master, tokens, labels, cfg, functools.partial(_q, precision=precision))
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
    scale = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    g = {k: x * scale for k, x in g.items()}
    warm = jnp.minimum(t / max(opt["warmup_steps"], 1), 1.0)
    prog = jnp.clip((t - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    lr = opt["lr"] * warm * (opt["min_lr_frac"]
                             + (1 - opt["min_lr_frac"]) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    b1, b2 = opt["b1"], opt["b2"]
    mu = {k: b1 * mu[k] + (1 - b1) * g[k] for k in g}
    nu = {k: b2 * nu[k] + (1 - b2) * jnp.square(g[k]) for k in g}
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    master = {k: master[k] - lr * (mu[k] / c1 / (jnp.sqrt(nu[k] / c2) + opt["eps"])
                                   + opt["weight_decay"] * master[k]) for k in g}
    return loss, master, mu, nu, _norms(g)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _loss(master, tokens, labels, cfg_items, precision):
    cfg = dict(cfg_items)
    return family(cfg).loss_fn(master, tokens, labels, cfg,
                               functools.partial(_q, precision=precision))


def _freeze(d):
    return tuple((k, _freeze(v) if isinstance(v, dict) else
                  tuple(v) if isinstance(v, list) else v) for k, v in sorted(d.items()))


def train_numbers(cfg: dict, seed: int, batches: List[Tuple[np.ndarray, np.ndarray]],
                  precision: str = "f32", fault: str = "") -> dict:
    """Losses of each step, the first step's clipped gradient norm per leaf,
    and each leaf's change after all steps, from the seed's initial weights."""
    shapes = family(cfg).param_shapes(cfg)

    def start():
        return {k: v.astype(jnp.float32) for k, v in
                gen.init_params_by_path(gen.prng_key(seed), shapes).items()}

    master = start()
    mu = {k: jnp.zeros_like(v) for k, v in master.items()}
    nu = {k: jnp.zeros_like(v) for k, v in master.items()}
    items = _freeze(cfg)
    losses, grad1 = [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        tokens, labels = np.array(tokens), np.array(labels)
        if fault == "half_batch":
            tokens, labels = tokens[: len(tokens) // 2], labels[: len(labels) // 2]
        elif fault == "token":
            tokens[0, 0] = (tokens[0, 0] + 1) % cfg["vocab_size"]
        if fault == "unchanged":
            losses.append(float(_loss(master, jnp.asarray(tokens), jnp.asarray(labels),
                                      items, precision)))
            continue
        loss, master, mu, nu, gn = _adam_step(master, mu, nu, jnp.float32(t),
                                              jnp.asarray(tokens), jnp.asarray(labels),
                                              items, precision)
        losses.append(float(loss))
        if grad1 is None:
            grad1 = {k: float(v) for k, v in gn.items()}
    if fault == "unchanged":   # Adam's first moment stays 0, the weights stay put
        zero = {k: 0.0 for k in master}
        return {"losses": losses, "grad1": zero, "change": dict(zero)}
    first = start()   # made again: the step's buffers were donated
    change = {k: float(jnp.sqrt(jnp.sum(jnp.square(master[k] - first[k]))))
              for k in master}
    return {"losses": losses, "grad1": grad1, "change": change}


# ------------------------------------------------------------------ digests
DIGEST_MULTS = (2654435761, 2246822519)
DIGEST_SALT = 0x9E3779B9
DIGEST_BLOCK_WORDS = 512   # each page's words are zero-padded to a multiple of this


@functools.lru_cache(maxsize=4)
def _digest_weights(n_words: int) -> np.ndarray:
    """A_m^(n-1-i) mod 2^32, as (2, n) uint32."""
    out = np.empty((2, n_words), dtype=np.uint32)
    for m, mult in enumerate(DIGEST_MULTS):
        acc = 1
        for i in range(n_words - 1, -1, -1):
            out[m, i] = acc
            acc = (acc * mult) & 0xFFFFFFFF
    return out


def page_digests(raw: np.ndarray, pages: np.ndarray, page_bytes: int) -> np.ndarray:
    """(len(pages), 2) uint32 fingerprints of the given pages of ``raw``.

    Page p's little-endian u32 words x_i, zero-padded to the page and then
    to a multiple of DIGEST_BLOCK_WORDS, give
    sum_i (x_i + SALT)·A_m^(W-1-i) mod 2^32 for the two multipliers.
    """
    n_words = page_bytes // 4
    n_words += (-n_words) % DIGEST_BLOCK_WORDS
    w = _digest_weights(n_words)
    out = np.empty((len(pages), 2), dtype=np.uint32)
    for j, p in enumerate(pages):
        buf = np.zeros(4 * n_words, dtype=np.uint8)
        chunk = raw[p * page_bytes:(p + 1) * page_bytes]
        buf[:len(chunk)] = chunk
        x = buf.view("<u4") + np.uint32(DIGEST_SALT)
        with np.errstate(over="ignore"):
            out[j] = (x[None, :] * w).sum(axis=1, dtype=np.uint32)
    return out
