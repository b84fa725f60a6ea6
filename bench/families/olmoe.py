"""OLMoE (arXiv:2409.02060): a decoder whose every MLP is a dropless mixture
of experts, one chip's share of them.

The reference's layer equations, written out in ``jax.numpy`` at float32
with ``highest`` matmul precision from the configuration file alone:

* RMSNorm with a learned scale (eps ``rms_norm_eps``) before attention,
  before the MLP and before the output head;
* full causal multi-head attention; q and k each normalised by an RMSNorm
  with a learned scale over the whole projection (all heads together),
  then rotary embeddings (rotate-half, theta from the file), scaled by
  1/sqrt(head size);
* the router: float32 logits over all ``published.num_experts`` experts,
  softmax, the top ``num_experts_per_tok`` kept with their probabilities
  as weights, not renormalised (``norm_topk_prob`` false);
* each expert this chip holds, ``[expert_offset, expert_offset +
  num_experts)``, runs its SwiGLU, (silu(x·Wg) * (x·Wi))·Wo, over every
  token, weighted by its router weight where the token chose it and by 0
  elsewhere: dense over the held experts, with no sort and no capacity;
* per layer the load-balancing loss E · Σ_i f_i · P_i (f_i: the share of
  the layer's N·k choices that name expert i; P_i: expert i's mean router
  probability), summed over layers and weighed by ``router_aux_loss_coef``;
* the untied output head over the vocabulary slice;
* loss: mean next-token cross-entropy plus ``z_loss``·mean(logsumexp²),
  plus the load-balancing term.

Departures from the published model, each also in the file's ``assumed``:
the experts that other chips hold add nothing (the chip's share of an
expert-parallel layer; nothing stands in for them), the vocabulary and
the output head are the slice this chip holds, the paper's router z-loss
is left out (as HuggingFace's ``OlmoeForCausalLM`` leaves it out), and a
``z_loss`` on the output logits is added, as the program's loss adds it.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def routed(cfg: dict) -> int:
    """The router's outputs: the experts of a layer as published (the
    reference gets nested groups of the file as (key, value) pairs)."""
    return dict(cfg["published"])["num_experts"]


def model_config(cfg: dict):
    """The program's model config with the file's sizes; refuses a file the
    program cannot run as stated."""
    # the program is imported here alone: the reference uses this module
    # and imports nothing of the program
    from repro.configs import get_config
    from repro.models import lm

    base = get_config(cfg["program_arch"])
    heads = cfg["num_attention_heads"]
    mc = dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"], n_heads=heads, n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], d_head=cfg["hidden_size"] // heads, dtype=cfg["dtype"],
        n_experts=routed(cfg), top_k=cfg["num_experts_per_tok"],
        experts_held=cfg["num_experts"], expert_offset=cfg["expert_offset"])
    stated = {
        "norm_kind": "rmsnorm", "norm_eps": cfg["rms_norm_eps"],
        "mlp_kind": "swiglu" if cfg["hidden_act"] == "silu" else None,
        "tie_embeddings": cfg["tie_word_embeddings"], "rope_theta": cfg["rope_theta"],
        "qkv_bias": cfg["attention_bias"], "qk_norm": True, "qk_norm_width": "full",
        "window": None, "block_pattern": ("attn",),
        "norm_topk_prob": cfg["norm_topk_prob"],
        "n_experts": routed(cfg), "top_k": cfg["num_experts_per_tok"],
        "n_held": cfg["num_experts"], "expert_offset": cfg["expert_offset"],
    }
    for key, want in stated.items():
        if getattr(mc, key) != want:
            raise ValueError(f"program config {key}={getattr(mc, key)!r}, file states {want!r}")
    losses = {"z_loss": lm.Z_LOSS, "router_aux_loss_coef": lm.AUX_LOSS}
    for key, program in losses.items():
        if cfg[key] != program:
            raise ValueError(f"the program's loss weighs {key} by {program!r}, "
                             f"file states {cfg[key]!r}")
    return mc


def param_shapes(cfg: dict) -> Dict[str, Tuple[tuple, object]]:
    """The weights as the program stores them: layers stacked on axis 0,
    router and norm scales in float32."""
    L, d, f, v = (cfg["num_hidden_layers"], cfg["hidden_size"],
                  cfg["intermediate_size"], cfg["vocab_size"])
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, held = routed(cfg), cfg["num_experts"]
    dh = d // h
    dt, f32 = jnp.dtype(cfg["dtype"]), jnp.dtype(jnp.float32)
    return {
        "embed/table": ((v, d), dt),
        "final_norm/scale": ((d,), f32),
        "groups/0/ffn/router": ((L, d, e), f32),
        "groups/0/ffn/wg": ((L, held, d, f), dt),
        "groups/0/ffn/wi": ((L, held, d, f), dt),
        "groups/0/ffn/wo": ((L, held, f, d), dt),
        "groups/0/mixer/k_norm": ((L, hkv, dh), f32),
        "groups/0/mixer/q_norm": ((L, h, dh), f32),
        "groups/0/mixer/wk": ((L, d, hkv, dh), dt),
        "groups/0/mixer/wo": ((L, h, dh, d), dt),
        "groups/0/mixer/wq": ((L, d, h, dh), dt),
        "groups/0/mixer/wv": ((L, d, hkv, dh), dt),
        "groups/0/norm1/scale": ((L, d), f32),
        "groups/0/norm2/scale": ((L, d), f32),
        "lm_head/w": ((d, v), dt),
    }


def _rms(x, scale, eps: float, axes=(-1,)):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axes, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def loss_fn(p, tokens, labels, cfg: dict, Q):
    ein = functools.partial(jnp.einsum, precision=HIGHEST)
    theta, eps = cfg["rope_theta"], cfg["rms_norm_eps"]
    E, K = routed(cfg), cfg["num_experts_per_tok"]
    first, held = cfg["expert_offset"], cfg["num_experts"]

    def layer(x, w):
        n1, wq, wk, wv, wo, qn, kn, n2, router, wg, wi, w2 = w
        h = _rms(x, n1, eps)
        q = _rope(_rms(ein("btd,dhk->bhtk", Q(h), Q(wq)), qn[None, :, None], eps, (1, 3)), theta)
        k = _rope(_rms(ein("btd,dhk->bhtk", Q(h), Q(wk)), kn[None, :, None], eps, (1, 3)), theta)
        v = ein("btd,dhk->bhtk", Q(h), Q(wv))
        s = ein("bhqd,bhkd->bhqk", Q(q), Q(k)) / math.sqrt(q.shape[-1])
        t = s.shape[-1]
        causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = ein("bhqk,bhkd->bhqd", Q(a), Q(v))
        x = x + ein("bhtk,hkd->btd", Q(o), Q(wo))
        h = _rms(x, n2, eps)
        probs = jax.nn.softmax(ein("btd,de->bte", Q(h), Q(router)), axis=-1)
        gates, chosen = jax.lax.top_k(probs, K)                      # (B, T, K)
        out = jnp.zeros_like(x)
        for j in range(held):
            gate = jnp.sum(jnp.where(chosen == first + j, gates, 0.0), axis=-1)
            u = (jax.nn.silu(ein("btd,df->btf", Q(h), Q(wg[j])))
                 * ein("btd,df->btf", Q(h), Q(wi[j])))
            out = out + gate[..., None] * ein("btf,fd->btd", Q(u), Q(w2[j]))
        share = jnp.mean(jax.nn.one_hot(chosen, E), axis=(0, 1, 2))   # f_i
        balance = E * jnp.sum(share * jnp.mean(probs, axis=(0, 1)))
        return x + out, balance

    x = p["embed/table"][tokens]
    balance = 0.0
    for l in range(cfg["num_hidden_layers"]):
        w = tuple(p[f"groups/0/{k}"][l] for k in (
            "norm1/scale", "mixer/wq", "mixer/wk", "mixer/wv", "mixer/wo", "mixer/q_norm",
            "mixer/k_norm", "norm2/scale", "ffn/router", "ffn/wg", "ffn/wi", "ffn/wo"))
        x, b = jax.checkpoint(layer)(x, w)
        balance = balance + b
    logits = ein("btd,dv->btv", Q(_rms(x, p["final_norm/scale"], eps)), Q(p["lm_head/w"]))
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (jnp.mean(lse - gold) + cfg["z_loss"] * jnp.mean(jnp.square(lse))
            + cfg["router_aux_loss_coef"] * balance)


def _expert_params(cfg: dict) -> int:
    """Parameters of one expert's SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def n_params(cfg: dict) -> int:
    """Parameters this chip holds of a model of ``cfg`` (the file's keys)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    attn = d * h * dh + 2 * d * hkv * dh + h * dh * d + (h + hkv) * dh
    moe = d * routed(cfg) + cfg["num_experts"] * _expert_params(cfg)
    head = 0 if cfg["tie_word_embeddings"] else d * v
    return cfg["num_hidden_layers"] * (attn + moe + 2 * d) + v * d + head + d


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs of one trained token: 6·N_active plus attention, 12·L·d·T.

    N_active is what a token multiplies on this chip: every parameter but
    the embedding table (a lookup) and the held experts, plus the experts a
    token is expected to reach here, k · held / E of them (routing uniform
    over the E experts).  The attention term counts the full T×T score and
    value products of the forward and backward passes (the usual MFU rule).
    Recomputation does not count.
    """
    held, E, K = cfg["num_experts"], routed(cfg), cfg["num_experts_per_tok"]
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    experts = L * _expert_params(cfg)
    dense = n_params(cfg) - cfg["vocab_size"] * d - held * experts
    return 6.0 * (dense + K * held / E * experts) + 12.0 * L * d * seq


def expert_gmm_flops(cfg: dict, rows: int) -> float:
    """FLOPs of the expert layer's grouped products over ``rows`` routed
    (token, choice) pairs, summed over layers: the gate, up and down
    products, each forward and for both of its gradients, 2·d·f a row each."""
    return 9 * 2.0 * cfg["hidden_size"] * cfg["intermediate_size"] * rows
