"""Shared transformer layers: norms, RoPE, MLP, GQA attention.

Everything is a pure function over explicit parameter dicts (leaves
created with ``param_util.leaf`` carry logical sharding axes).  Covers
the dense-family variance across the assigned archs: qk-norm per head
(qwen3) or over the whole projection (olmoe),
QKV bias (qwen1.5), non-parametric LN (olmo), SWA (danube3), local
attention (recurrentgemma), GQA everywhere.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import constrain
from repro.kernels import ops as kops
from repro.models.config import ModelConfig
from repro.models.param_util import leaf, normal, ones, zeros

# Blockwise attention kicks in above this many kv positions (keeps the
# 32k prefill cells inside per-device memory without a Pallas dependency
# in the differentiable path).  Overridable: materializing the f32 scores
# of 4k positions is the peak-memory term of a 4k training step (16 heads
# of OLMoE at 4096 keep 1 GiB of scores a layer for the backward pass).
import os as _os

BLOCKWISE_KV_THRESHOLD = int(_os.environ.get("REPRO_BLOCKWISE_THRESHOLD", 2048))
BLOCKWISE_CHUNK = int(_os.environ.get("REPRO_BLOCKWISE_CHUNK", 1024))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, dtype) -> Dict:
    if cfg.norm_kind == "rmsnorm":
        return {"scale": leaf(ones((cfg.d_model,), jnp.float32), "embed")}
    if cfg.norm_kind == "layernorm":
        return {
            "scale": leaf(ones((cfg.d_model,), jnp.float32), "embed"),
            "bias": leaf(zeros((cfg.d_model,), jnp.float32), "embed"),
        }
    if cfg.norm_kind == "nonparam_ln":  # OLMo: no learnable affine
        return {}
    raise ValueError(cfg.norm_kind)


def rms_eps(cfg: ModelConfig) -> float:
    """The eps of RMSNorm and of QK-norm: the configuration's, else 1e-6."""
    return 1e-6 if cfg.norm_eps is None else cfg.norm_eps


def apply_norm(p: Dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    if cfg.norm_kind == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + rms_eps(cfg))
        return (xf * p["scale"]).astype(dt)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, -1, keepdims=True)
    xf = (xf - mean) * jax.lax.rsqrt(var + (1e-5 if cfg.norm_eps is None else cfg.norm_eps))
    if cfg.norm_kind == "layernorm":
        xf = xf * p["scale"] + p["bias"]
    return xf.astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, H, T, D); positions: (T,) absolute token positions."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # (T, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    xr1 = x1 * cos - x2 * sin
    xr2 = x2 * cos + x1 * sin
    return jnp.concatenate([xr1, xr2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(rng, cfg: ModelConfig, dtype) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(rng, 3)
    p = {
        "wi": leaf(normal(ks[0], (d, f), dtype), "embed", "mlp"),
        "wo": leaf(normal(ks[1], (f, d), dtype), "mlp", "embed"),
    }
    if cfg.mlp_kind in ("swiglu", "geglu"):
        p["wg"] = leaf(normal(ks[2], (d, f), dtype), "embed", "mlp")
    return p


def apply_mlp(p: Dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    h = jnp.einsum("btd,df->btf", x, p["wi"])
    if cfg.mlp_kind == "swiglu":
        h = jax.nn.silu(jnp.einsum("btd,df->btf", x, p["wg"])) * h
    elif cfg.mlp_kind == "geglu":
        h = jax.nn.gelu(jnp.einsum("btd,df->btf", x, p["wg"])) * h
    else:
        h = jax.nn.gelu(h)
    h = constrain(h, "batch", None, "mlp_act")
    return jnp.einsum("btf,fd->btd", h, p["wo"])


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attention(rng, cfg: ModelConfig, dtype, cross: bool = False) -> Dict:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 8)
    p = {
        "wq": leaf(normal(ks[0], (d, h, dh), dtype), "embed", "q_heads", "head"),
        "wk": leaf(normal(ks[1], (d, hkv, dh), dtype), "embed", "kv_heads", "head"),
        "wv": leaf(normal(ks[2], (d, hkv, dh), dtype), "embed", "kv_heads", "head"),
        "wo": leaf(normal(ks[3], (h, dh, d), dtype), "q_heads", "head", "embed"),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = leaf(zeros((h, dh), dtype), "q_heads", "head")
        p["bk"] = leaf(zeros((hkv, dh), dtype), "kv_heads", "head")
        p["bv"] = leaf(zeros((hkv, dh), dtype), "kv_heads", "head")
    if cfg.qk_norm and not cross:
        if cfg.qk_norm_width == "full":
            p["q_norm"] = leaf(ones((h, dh), jnp.float32), "q_heads", "head")
            p["k_norm"] = leaf(ones((hkv, dh), jnp.float32), "kv_heads", "head")
        else:
            p["q_scale"] = leaf(ones((dh,), jnp.float32), "head")
            p["k_scale"] = leaf(ones((dh,), jnp.float32), "head")
    return p


def _head_rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype)


def _full_rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm of each token's whole projection, all heads together:
    x (B, H, T, Dh), scale (H, Dh)."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, (1, 3), keepdims=True) + eps)
    return (xf * scale[None, :, None, :]).astype(x.dtype)


def _project_qkv(p, cfg, x, positions, apply_rope: bool = True):
    q = jnp.einsum("btd,dhk->bhtk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bhtk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bhtk", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"][None, :, None, :]
        k = k + p["bk"][None, :, None, :]
        v = v + p["bv"][None, :, None, :]
    if "q_scale" in p:
        q = _head_rmsnorm(q, p["q_scale"], rms_eps(cfg))
        k = _head_rmsnorm(k, p["k_scale"], rms_eps(cfg))
    if "q_norm" in p:
        q = _full_rmsnorm(q, p["q_norm"], rms_eps(cfg))
        k = _full_rmsnorm(k, p["k_norm"], rms_eps(cfg))
    if apply_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _blockwise_attention(q, k, v, *, causal, window, q_offset, softcap, chunk):
    """Online-softmax attention, chunked over KV (pure jnp, differentiable).

    Memory O(Tq * chunk) per head instead of O(Tq * Tk): the 32k cells
    and the remat policy rely on this.  The backward pass recomputes each
    chunk's scores instead of keeping them, so training keeps O(Tq * D)
    per chunk too.  Mirrors ``kernels/flash_attention`` (which serves the
    non-differentiable TPU serving path).
    """
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    group = Hq // Hkv
    pad_k = (-Tk) % chunk
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_chunks = k.shape[2] // chunk
    qf = (q.astype(jnp.float32) * (D ** -0.5)).reshape(B, Hkv, group, Tq, D)
    kc = k.reshape(B, Hkv, n_chunks, chunk, D)
    vc = v.reshape(B, Hkv, n_chunks, chunk, D)
    qpos = q_offset + jnp.arange(Tq)

    def step(carry, inp):
        m, l, acc = carry
        kj, vj, j = inp
        kj = kj.astype(jnp.float32)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kj)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        kpos = j * chunk + jnp.arange(chunk)
        mask = kpos[None, :] < Tk
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        pexp = jnp.exp(s - m_new[..., None])
        pexp = jnp.where(mask[None, None, None], pexp, 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + pexp.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", pexp, vj.astype(jnp.float32)
        )
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, group, Tq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, group, Tq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, group, Tq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step),
        (m0, l0, a0),
        (kc.transpose(2, 0, 1, 3, 4), vc.transpose(2, 0, 1, 3, 4),
         jnp.arange(n_chunks)),
    )
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).reshape(B, Hq, Tq, D)
    return out.astype(q.dtype)


def attention_core(q, k, v, *, causal, window, q_offset, softcap,
                   kv_positions: Optional[jax.Array] = None,
                   q_positions: Optional[jax.Array] = None):
    """Dispatch dense / blockwise / cache attention.

    ``kv_positions``: absolute positions of cache slots for decode
    (entries < 0 are empty slots).  When given, masking uses positions
    (``q_positions``) rather than indices.
    """
    Tk = k.shape[2]
    if kv_positions is not None:
        # decode path: dense scores against the cache (Tq is tiny).
        # KV operands stay in the cache dtype with f32 accumulation
        # (preferred_element_type) — materializing an f32 copy of a
        # multi-GiB cache would double decode HBM (observed as temp
        # blow-up in the dry-run memory analysis).
        B, Hq, Tq, D = q.shape
        Hkv = k.shape[1]
        group = Hq // Hkv
        qf = (q.astype(k.dtype) * (D ** -0.5)).reshape(B, Hkv, group, Tq, D)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, k,
                       preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_positions
        mask = kv_positions[None, :] >= 0
        if causal:
            mask = mask & (kv_positions[None, :] <= qpos[:, None])
        if window is not None:
            mask = mask & (kv_positions[None, :] > qpos[:, None] - window)
        s = jnp.where(mask[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(k.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.reshape(B, Hq, Tq, D).astype(q.dtype)
    if Tk > BLOCKWISE_KV_THRESHOLD:
        return _blockwise_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            softcap=softcap, chunk=BLOCKWISE_CHUNK,
        )
    return kops._attention_ref(q, k, v, causal, window, q_offset, softcap)


def apply_attention(
    p: Dict,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    kind: str = "attn",              # attn | local | swa
    causal: bool = True,
    cache: Optional[Dict] = None,    # {"k","v","pos"}; decode/prefill KV cache
    cache_index: Optional[jax.Array] = None,  # slot to write new kv at
) -> Tuple[jax.Array, Optional[Dict]]:
    window = cfg.window if kind in ("local", "swa") else None
    is_decode = cache is not None and x.shape[1] == 1
    q, k, v = _project_qkv(p, cfg, x, positions)
    q = constrain(q, "batch", "heads_act", None, None)
    k = constrain(k, "batch", "kv_act", None, None)
    v = constrain(v, "batch", "kv_act", None, None)

    if is_decode and _use_shard_decode():
        from repro.distributed import axes as _AX
        from repro.distributed.decode_attn import sharded_decode_attention
        out, new_cache = sharded_decode_attention(
            _AX.current_mesh(), q, cache, k, v, positions,
            causal=causal, window=window, softcap=cfg.softcap,
        )
        y = jnp.einsum("bhtk,hkd->btd", out, p["wo"])
        return y, new_cache

    new_cache = None
    kv_positions = None
    if cache is not None:
        cache_len = cache["k"].shape[2]
        Tq = k.shape[2]
        if Tq >= cache_len:
            # Prefill longer than a window-limited cache: only the last
            # ``cache_len`` positions survive.  Slot invariant is
            # slot = pos % cache_len, so the window is rolled into place.
            kw = k[:, :, -cache_len:].astype(cache["k"].dtype)
            vw = v[:, :, -cache_len:].astype(cache["v"].dtype)
            pw = positions[-cache_len:]
            shift = pw[0] % cache_len
            ck = jnp.roll(kw, shift, axis=2)
            cv = jnp.roll(vw, shift, axis=2)
            cpos = jnp.roll(pw, shift)
        else:
            # Fits: contiguous write at slot = pos % cache_len (decode
            # steps and from-zero prefills never wrap).
            slot = cache_index if cache_index is not None else positions[0] % cache_len
            ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                              (0, 0, slot, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                              (0, 0, slot, 0))
            cpos = jax.lax.dynamic_update_slice(cache["pos"], positions, (slot,))
        new_cache = {"k": ck, "v": cv, "pos": cpos}
        if is_decode:
            # decode: attend over the cache (positions mask empty slots)
            k, v, kv_positions = ck, cv, cpos
    out = attention_core(
        q, k, v, causal=causal, window=window, q_offset=0,
        softcap=cfg.softcap, kv_positions=kv_positions, q_positions=positions,
    )
    y = jnp.einsum("bhtk,hkd->btd", out, p["wo"])
    return y, new_cache


def apply_cross_attention(
    p: Dict, cfg: ModelConfig, x: jax.Array, memory_kv: Tuple[jax.Array, jax.Array]
) -> jax.Array:
    """Decoder cross-attention over precomputed encoder K/V."""
    q = jnp.einsum("btd,dhk->bhtk", x, p["wq"])
    k, v = memory_kv
    out = attention_core(q, k, v, causal=False, window=None, q_offset=0, softcap=None)
    return jnp.einsum("bhtk,hkd->btd", out, p["wo"])


def cross_attention_memory(p: Dict, cfg: ModelConfig, enc_out: jax.Array):
    k = jnp.einsum("btd,dhk->bhtk", enc_out, p["wk"])
    v = jnp.einsum("btd,dhk->bhtk", enc_out, p["wv"])
    return (k, v)


def _use_shard_decode() -> bool:
    from repro.distributed import axes as _AX

    rules = _AX.current_rules()
    mesh = _AX.current_mesh()
    return bool(rules and rules.get("__shard_decode__")
                and mesh is not None and "model" in mesh.axis_names)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Dict:
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": zeros((batch, hkv, max_len, dh), dtype),
        "v": zeros((batch, hkv, max_len, dh), dtype),
        "pos": -jnp.ones((max_len,), jnp.int32),
    }
