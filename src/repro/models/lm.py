"""Decoder-only LM assembly: embed -> pattern blocks -> norm -> logits.

Layers follow ``cfg.block_pattern`` cycled over ``cfg.n_layers``; whole
pattern groups are stacked and driven by ``lax.scan`` (compact HLO for
80-layer models; activation-checkpointing wraps the group body), with
any remainder layers unrolled.

Three entry points per model:

* ``loss_fn``    — next-token CE (+ MoE aux, + z-loss) for train_4k;
* ``prefill``    — full-sequence forward that fills the KV/state caches
                   (prefill_32k);
* ``decode_step``— one token against the caches (decode_32k/long_500k).

VLM (internvl2): ``batch["vision_embeds"]`` (stub ViT output) replaces
the embeddings of the first ``n_frontend_tokens`` positions.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import constrain
from repro.models import layers as L
from repro.models import moe as M
from repro.models import recurrent as R
from repro.models.config import ModelConfig
from repro.models.param_util import leaf, normal, split_tree, stack_trees

ATTN_KINDS = ("attn", "local", "swa")
Z_LOSS = 1e-4      # weight of mean(logsumexp²) of the output logits
AUX_LOSS = 1e-2    # weight of the MoE load-balancing loss, summed over layers


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# block init / apply
# ---------------------------------------------------------------------------


def init_block(rng, cfg: ModelConfig, kind: str) -> Dict:
    dt = _dtype(cfg)
    ks = jax.random.split(rng, 3)
    p: Dict = {"norm1": L.init_norm(cfg, dt)}
    if kind in ATTN_KINDS:
        p["mixer"] = L.init_attention(ks[0], cfg, dt)
    elif kind == "rglru":
        p["mixer"] = R.init_rglru(ks[0], cfg, dt)
    elif kind == "mlstm":
        p["mixer"] = R.init_mlstm(ks[0], cfg, dt)
    elif kind == "slstm":
        p["mixer"] = R.init_slstm(ks[0], cfg, dt)
    else:
        raise ValueError(kind)
    if cfg.d_ff > 0 and kind not in ("mlstm", "slstm"):
        p["norm2"] = L.init_norm(cfg, dt)
        p["ffn"] = M.init_moe(ks[1], cfg, dt) if cfg.moe else L.init_mlp(ks[1], cfg, dt)
    return p


def apply_block(
    p: Dict,
    cfg: ModelConfig,
    kind: str,
    x: jax.Array,
    positions: jax.Array,
    cache: Optional[Dict],
):
    """Returns (x, new_cache, stats): ``stats["aux"]`` is the MoE
    load-balancing loss (0 without experts), ``stats["expert_rows"]`` the
    pairs routed to the held experts (MoE only)."""
    h = L.apply_norm(p["norm1"], cfg, x)
    if kind in ATTN_KINDS:
        y, new_cache = L.apply_attention(
            p["mixer"], cfg, h, positions, kind=kind, cache=cache
        )
    elif kind == "rglru":
        y, new_cache = R.apply_rglru(p["mixer"], cfg, h, cache)
    elif kind == "mlstm":
        y, new_cache = R.apply_mlstm(p["mixer"], cfg, h, cache)
    elif kind == "slstm":
        y, new_cache = R.apply_slstm(p["mixer"], cfg, h, cache)
    else:
        raise ValueError(kind)
    x = x + y
    stats = {"aux": jnp.zeros((), jnp.float32)}
    if "ffn" in p:
        h2 = L.apply_norm(p["norm2"], cfg, x)
        if cfg.moe:
            y2, aux, rows = M.apply_moe(p["ffn"], cfg, h2)
            stats = {"aux": aux, "expert_rows": rows}
        else:
            y2 = L.apply_mlp(p["ffn"], cfg, h2)
        x = x + y2
    x = constrain(x, "batch", None, "embed_act")
    return x, new_cache, stats


def init_cache_entry(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    dt = _dtype(cfg)
    if kind in ATTN_KINDS:
        length = max_len if kind == "attn" or cfg.window is None else min(max_len, cfg.window)
        return L.init_kv_cache(cfg, batch, length, dt)
    if kind == "rglru":
        return R.init_rglru_state(cfg, batch, dt)
    if kind == "mlstm":
        return R.init_mlstm_state(cfg, batch, dt)
    if kind == "slstm":
        return R.init_slstm_state(cfg, batch, dt)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# whole-model params
# ---------------------------------------------------------------------------


def _pattern_layout(cfg: ModelConfig) -> Tuple[int, Tuple[str, ...]]:
    """(n_groups, remainder_kinds)."""
    P = len(cfg.block_pattern)
    return cfg.n_layers // P, tuple(
        cfg.block_pattern[i % P] for i in range(cfg.n_layers - cfg.n_layers % P, cfg.n_layers)
    )


def init_lm(rng, cfg: ModelConfig):
    """Returns a tree with (array, axes) leaves; split with split_tree."""
    dt = _dtype(cfg)
    n_groups, rest = _pattern_layout(cfg)
    ks = iter(jax.random.split(rng, 4 + cfg.n_layers))
    tree: Dict = {
        "embed": {"table": leaf(normal(next(ks), (cfg.vocab_size, cfg.d_model), dt),
                                "vocab", "embed")},
        "final_norm": L.init_norm(cfg, dt),
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = {"w": leaf(normal(next(ks), (cfg.d_model, cfg.vocab_size), dt),
                                     "embed", "vocab")}
    groups = []
    if n_groups > 0:
        for p_idx, kind in enumerate(cfg.block_pattern):
            per_group = [init_block(next(ks), cfg, kind) for _ in range(n_groups)]
            groups.append(stack_trees(per_group))
    tree["groups"] = groups
    tree["rest"] = [init_block(next(ks), cfg, kind) for kind in rest]
    return tree


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------


def _embed(params, cfg: ModelConfig, batch: Dict) -> jax.Array:
    tokens = batch["tokens"]
    x = params["embed"]["table"][tokens]
    if cfg.frontend is not None and "vision_embeds" in batch:
        fe = batch["vision_embeds"].astype(x.dtype)
        n = fe.shape[1]
        x = jnp.concatenate([fe, x[:, n:]], axis=1)
    return constrain(x, "batch", None, "embed_act")


def _logits(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = L.apply_norm(params["final_norm"], cfg, x)
    if cfg.tie_embeddings:
        w = params["embed"]["table"].T
    else:
        w = params["lm_head"]["w"]
    logits = jnp.einsum("btd,dv->btv", x, w)
    return constrain(logits, "batch", None, "vocab_act")


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn)
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    raise ValueError(policy)


def apply_stack_train(params, cfg: ModelConfig, x, positions, remat_policy="none"):
    """Training/prefill-style pass without caches. Returns (x, stats): each
    of ``apply_block``'s stats summed over layers."""
    n_groups, rest = _pattern_layout(cfg)
    total = {"aux": jnp.zeros((), jnp.float32)}

    def add(acc, stats):
        return {k: acc[k] + v if k in acc else v for k, v in stats.items()}

    if n_groups > 0:
        def group_body(x, group_params):
            acc = {"aux": jnp.zeros((), jnp.float32)}
            for p_idx, kind in enumerate(cfg.block_pattern):
                x, _, s = apply_block(group_params[p_idx], cfg, kind, x, positions, None)
                acc = add(acc, s)
            return x, acc

        body = _remat(group_body, remat_policy)
        x, stats = jax.lax.scan(lambda c, xs: body(c, xs), x, tuple(params["groups"]))
        total = add(total, {k: s.sum() for k, s in stats.items()})
    for p_rest, kind in zip(params["rest"], _pattern_layout(cfg)[1]):
        x, _, s = apply_block(p_rest, cfg, kind, x, positions, None)
        total = add(total, s)
    return x, total


def apply_stack_cached(params, cfg: ModelConfig, x, positions, cache):
    """Prefill/decode pass threading caches. Returns (x, new_cache)."""
    n_groups, rest_kinds = _pattern_layout(cfg)
    new_cache: Dict = {"groups": [], "rest": []}
    if n_groups > 0:
        def group_body(x, xs):
            group_params, group_cache = xs
            new_entries = []
            for p_idx, kind in enumerate(cfg.block_pattern):
                x, nc, _ = apply_block(
                    group_params[p_idx], cfg, kind, x, positions, group_cache[p_idx]
                )
                new_entries.append(nc)
            return x, tuple(new_entries)

        x, new_group_cache = jax.lax.scan(
            group_body, x, (tuple(params["groups"]), tuple(cache["groups"]))
        )
        new_cache["groups"] = list(new_group_cache)
    for p_rest, kind, c_rest in zip(params["rest"], rest_kinds, cache["rest"]):
        x, nc, _ = apply_block(p_rest, cfg, kind, x, positions, c_rest)
        new_cache["rest"].append(nc)
    return x, new_cache


def init_lm_cache(cfg: ModelConfig, batch: int, max_len: int):
    n_groups, rest_kinds = _pattern_layout(cfg)
    groups = []
    if n_groups > 0:
        for kind in cfg.block_pattern:
            entries = [init_cache_entry(cfg, kind, batch, max_len) for _ in range(n_groups)]
            groups.append(jax.tree.map(lambda *ls: jnp.stack(ls), *entries)
                          if n_groups > 1 else jax.tree.map(lambda l: l[None], entries[0]))
    rest = [init_cache_entry(cfg, kind, batch, max_len) for kind in rest_kinds]
    return {"groups": groups, "rest": rest}


# ---------------------------------------------------------------------------
# public heads
# ---------------------------------------------------------------------------


def lm_loss(params, cfg: ModelConfig, batch: Dict, remat_policy="none"):
    """Next-token CE over ``labels`` (mask: labels < 0). Returns (loss, metrics)."""
    x = _embed(params, cfg, batch)
    T = x.shape[1]
    positions = jnp.arange(T)
    x, stats = apply_stack_train(params, cfg, x, positions, remat_policy)
    aux = stats["aux"]
    logits = _logits(params, cfg, x).astype(jnp.float32)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    lbl = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, lbl[..., None], axis=-1)[..., 0]
    ce = (lse - gold) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = ce.sum() / denom
    zloss = Z_LOSS * ((lse * mask) ** 2).sum() / denom
    total = loss + zloss + AUX_LOSS * aux
    metrics = {"ce": loss, "zloss": zloss, "aux": aux, "tokens": denom}
    if "expert_rows" in stats:
        metrics["expert_rows"] = stats["expert_rows"]
    return total, metrics


def lm_prefill(params, cfg: ModelConfig, batch: Dict, cache):
    """Forward the prompt, filling caches; returns (last_logits, cache)."""
    x = _embed(params, cfg, batch)
    T = x.shape[1]
    positions = jnp.arange(T)
    x, cache = apply_stack_cached(params, cfg, x, positions, cache)
    logits = _logits(params, cfg, x[:, -1:, :])
    return logits[:, 0], cache


def lm_decode_step(params, cfg: ModelConfig, token: jax.Array, pos: jax.Array, cache):
    """One decode step. token: (B,) int32; pos: () int32 absolute position."""
    x = params["embed"]["table"][token][:, None, :]
    positions = jnp.full((1,), pos, jnp.int32)
    x, cache = apply_stack_cached(params, cfg, x, positions, cache)
    logits = _logits(params, cfg, x)
    return logits[:, 0], cache
