"""Mixture-of-experts FFN, dropless, over the experts a chip holds
(olmoe-1b-7b, granite-moe-1b-a400m).

Expert parallelism, as one chip sees it: the router scores every token
against all ``n_experts`` of the layer, and the chip holds experts
``[expert_offset, expert_offset + n_held)`` and computes their part of the
output for the (token, choice) pairs routed to them.  What the absent
experts add is left out, and nothing stands in for the chips that hold
them; with every expert held (the default) the layer is the whole MoE.

* ``moe.route``: float32 router, softmax over all experts, top-k; the k
  weights are renormalised to sum to 1 only where ``norm_topk_prob``
  (granite renormalises, OLMoE does not);
* ``moe.experts``: the N·k pairs sorted by expert, the held experts' pairs
  first, and SwiGLU as three grouped matrix products over the held
  experts (``kernels.ops.grouped_matmul``).  There is no capacity: no pair
  is dropped however uneven the routing, and the pairs of absent experts
  cost no product;
* ``moe.combine``: each pair's output back at its token, weighted by its
  router weight, summed in float32.

The load-balancing loss of a layer is computed from the router over all
experts, so every share computes the same one:
``E · Σ_i f_i · P_i``, with ``f_i`` the share of the layer's N·k
assignments that go to expert i and ``P_i`` expert i's mean router
probability over the N tokens (the Switch Transformer form, which OLMoE
uses; 1 under uniform routing).  The model sums it over layers.
HuggingFace's ``load_balancing_loss_func`` differs in two ways: it pools
the tokens of all layers before taking ``f`` and ``P``, and it does not
divide ``f`` by k, so it reads k · E · Σ_i f̄_i · P̄_i.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models.config import ModelConfig
from repro.models.param_util import leaf, normal


def init_moe(rng, cfg: ModelConfig, dtype) -> Dict:
    d, f, e, held = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.n_held
    ks = jax.random.split(rng, 4)
    return {
        "router": leaf(normal(ks[0], (d, e), jnp.float32), "embed", "experts"),
        "wi": leaf(normal(ks[1], (held, d, f), dtype), "experts", "embed", "mlp"),
        "wg": leaf(normal(ks[2], (held, d, f), dtype), "experts", "embed", "mlp"),
        "wo": leaf(normal(ks[3], (held, f, d), dtype), "experts", "mlp", "embed"),
    }


@jax.custom_vjp
def _permute(rows: jax.Array, perm: jax.Array, inv: jax.Array) -> jax.Array:
    """``rows[perm]`` for a permutation ``perm`` whose inverse is ``inv``:
    the gradient is a gather too, not a scatter."""
    return jnp.take(rows, perm, axis=0)


def _permute_fwd(rows, perm, inv):
    return jnp.take(rows, perm, axis=0), inv


def _permute_bwd(inv, g):
    return jnp.take(g, inv, axis=0), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def apply_moe(p: Dict, cfg: ModelConfig, x: jax.Array
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, T, D) -> (out, load-balancing loss, pairs routed to the held experts)."""
    B, T, D = x.shape
    N, E, K, H = B * T, cfg.n_experts, cfg.top_k, cfg.n_held
    xf = x.reshape(N, D)
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), p["router"],
                            precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(probs, K)                   # (N, K)
        if cfg.norm_topk_prob:
            gates = gates / jnp.sum(gates, -1, keepdims=True)
        chosen = jnp.bincount(experts.reshape(-1), length=E) / (N * K)
        aux = E * jnp.sum(chosen * jnp.mean(probs, axis=0))
        local = experts.reshape(-1) - cfg.expert_offset            # (N*K,)
        held = (local >= 0) & (local < H)
        group = jnp.where(held, local, H)     # absent experts' pairs: one group past the last
        order = jnp.argsort(group, stable=True)
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(N * K, dtype=order.dtype))
        sizes = jnp.bincount(group, length=H + 1)[:H].astype(jnp.int32)
    with jax.named_scope("moe.experts"):
        xs = _permute(jnp.repeat(xf, K, axis=0), order, inv)    # pairs, by expert
        h = (jax.nn.silu(kops.grouped_matmul(xs, p["wg"], sizes))
             * kops.grouped_matmul(xs, p["wi"], sizes))
        ys = kops.grouped_matmul(h, p["wo"], sizes)
    with jax.named_scope("moe.combine"):
        w = jnp.where(held.reshape(N, K), gates, 0.0)
        out = _combine(ys, w, order, inv).astype(x.dtype)
    return out.reshape(B, T, D), aux.astype(jnp.float32), jnp.sum(sizes)


@jax.checkpoint
def _combine(ys, w, order, inv):
    """Each pair's output at its token, weighted, summed in float32.  Kept
    for the backward pass are its inputs, not the float32 copy of the
    N·k outputs."""
    N, K = w.shape
    y = _permute(ys, inv, order).reshape(N, K, -1)
    return jnp.einsum("nkd,nk->nd", y.astype(jnp.float32), w)
