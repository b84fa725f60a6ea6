"""OLMo (arXiv:2402.00838): a dense decoder with non-parametric LayerNorm.

The reference's layer equations, written out in ``jax.numpy`` at float32
with ``highest`` matmul precision from the configuration file alone:

* non-parametric LayerNorm (eps 1e-5) before attention, before the MLP and
  before the output head;
* full causal multi-head attention with rotary embeddings (rotate-half,
  theta from the file) on q and k, scaled by 1/sqrt(head size);
* SwiGLU MLP: (silu(x·Wg) * (x·Wi))·Wo;
* the output head multiplies by the tied embedding table;
* loss: mean next-token cross-entropy plus ``z_loss``·mean(logsumexp²).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def model_config(cfg: dict):
    """The program's model config with the file's sizes; refuses a file the
    program cannot run as stated."""
    # the program is imported here alone: the reference uses this module
    # and imports nothing of the program
    from repro.configs import get_config

    base = get_config(cfg["program_arch"])
    heads = cfg["num_attention_heads"]
    mc = dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"], n_heads=heads,
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        d_head=cfg["hidden_size"] // heads, dtype=cfg["dtype"])
    stated = {
        "norm_kind": "nonparam_ln", "mlp_kind": "swiglu" if cfg["hidden_act"] == "silu" else None,
        "tie_embeddings": cfg["tie_word_embeddings"], "rope_theta": cfg["rope_theta"],
        "qkv_bias": cfg["attention_bias"], "qk_norm": False, "window": None,
        "block_pattern": ("attn",), "n_experts": 0,
    }
    for key, want in stated.items():
        if getattr(mc, key) != want:
            raise ValueError(f"program config {key}={getattr(mc, key)!r}, file states {want!r}")
    return mc


def param_shapes(cfg: dict) -> Dict[str, Tuple[tuple, object]]:
    """The weights as the program stores them: layers stacked on axis 0."""
    L, d, f, v = (cfg["num_hidden_layers"], cfg["hidden_size"],
                  cfg["intermediate_size"], cfg["vocab_size"])
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    dt = jnp.dtype(cfg["dtype"])
    return {
        "embed/table": ((v, d), dt),
        "groups/0/ffn/wg": ((L, d, f), dt),
        "groups/0/ffn/wi": ((L, d, f), dt),
        "groups/0/ffn/wo": ((L, f, d), dt),
        "groups/0/mixer/wk": ((L, d, hkv, dh), dt),
        "groups/0/mixer/wo": ((L, h, dh, d), dt),
        "groups/0/mixer/wq": ((L, d, h, dh), dt),
        "groups/0/mixer/wv": ((L, d, hkv, dh), dt),
    }


def _ln(x):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-5)


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
    theta = cfg["rope_theta"]

    def layer(x, w):
        wq, wk, wv, wo, wg, wi, w2 = w
        h = _ln(x)
        q = _rope(ein("btd,dhk->bhtk", Q(h), Q(wq)), theta)
        k = _rope(ein("btd,dhk->bhtk", Q(h), Q(wk)), theta)
        v = ein("btd,dhk->bhtk", Q(h), Q(wv))
        s = ein("bhqd,bhkd->bhqk", Q(q), Q(k)) / math.sqrt(q.shape[-1])
        t = s.shape[-1]
        causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = ein("bhqk,bhkd->bhqd", Q(a), Q(v))
        x = x + ein("bhtk,hkd->btd", Q(o), Q(wo))
        h = _ln(x)
        u = jax.nn.silu(ein("btd,df->btf", Q(h), Q(wg))) * ein("btd,df->btf", Q(h), Q(wi))
        return x + ein("btf,fd->btd", Q(u), Q(w2))

    table = p["embed/table"]
    x = table[tokens]
    for l in range(cfg["num_hidden_layers"]):
        w = tuple(p[k][l] for k in ("groups/0/mixer/wq", "groups/0/mixer/wk",
                                    "groups/0/mixer/wv", "groups/0/mixer/wo",
                                    "groups/0/ffn/wg", "groups/0/ffn/wi",
                                    "groups/0/ffn/wo"))
        x = jax.checkpoint(layer)(x, w)
    logits = ein("btd,vd->btv", Q(_ln(x)), Q(table))
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold) + cfg["z_loss"] * jnp.mean(jnp.square(lse))


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
