"""Hand-scheduled sharded decode attention (shard_map).

GSPMD struggles with seq-sharded KV caches at decode: the
dynamic-update-slice at a traced position and the softmax over the
sharded axis lower to cache-sized gathers (EXPERIMENTS.md §Perf-D).
This module schedules the step explicitly over the "model" axis:

* the cache stays sharded over its sequence dim; the new token's KV is
  written **locally** by the shard that owns the slot (a one-slot
  dynamic-update-slice with a where-select — no cross-shard traffic);
* each shard runs an online-softmax (flash) pass over its own chunk;
* shards combine with three tiny collectives: pmax of the running max
  and psums of the rescaled normalizer/accumulator —
  O(B·H·dh) bytes per layer instead of O(cache).

The query is replicated over "model" (it is one token); batch stays
sharded over the DP axes.  Exact up to float associativity — verified
against the reference decode path in tests/test_decode_attn.py.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

AXIS = "model"


def _local_step(q, ck, cv, cpos, k_new, v_new, positions,
                *, causal, window, softcap, n_shards):
    """Runs on ONE shard: local write + local flash + global combine."""
    ax = jax.lax.axis_index(AXIS)
    B, Hq, Tq, D = q.shape
    Hkv = ck.shape[1]
    group = Hq // Hkv
    local_len = ck.shape[2]
    pos = positions[0]
    slot = pos % (local_len * n_shards)
    owner = slot // local_len
    local_slot = slot % local_len
    mine = ax == owner

    # -- local in-place write: owner takes the new KV, others rewrite the
    #    existing slot value (no cross-shard traffic, alias-friendly) --
    old_k = jax.lax.dynamic_slice(ck, (0, 0, local_slot, 0), (B, Hkv, 1, D))
    old_v = jax.lax.dynamic_slice(cv, (0, 0, local_slot, 0), (B, Hkv, 1, D))
    wk = jnp.where(mine, k_new.astype(ck.dtype), old_k)
    wv = jnp.where(mine, v_new.astype(cv.dtype), old_v)
    ck = jax.lax.dynamic_update_slice(ck, wk, (0, 0, local_slot, 0))
    cv = jax.lax.dynamic_update_slice(cv, wv, (0, 0, local_slot, 0))
    old_p = jax.lax.dynamic_slice(cpos, (local_slot,), (1,))
    cpos = jax.lax.dynamic_update_slice(
        cpos, jnp.where(mine, positions, old_p), (local_slot,))

    # -- local flash over this shard's chunk --
    qf = (q.astype(ck.dtype) * (D ** -0.5)).reshape(B, Hkv, group, Tq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, ck,
                   preferred_element_type=jnp.float32)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    mask = cpos[None, :] >= 0
    if causal:
        mask = mask & (cpos[None, :] <= positions[:, None])
    if window is not None:
        mask = mask & (cpos[None, :] > positions[:, None] - window)
    s = jnp.where(mask[None, None, None], s, -1e30)
    m_loc = jnp.max(s, axis=-1)                            # (B,Hkv,g,Tq)
    p = jnp.exp(s - m_loc[..., None])
    p = jnp.where(mask[None, None, None], p, 0.0)
    l_loc = p.sum(-1)
    acc_loc = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(cv.dtype), cv,
                         preferred_element_type=jnp.float32)

    # -- tiny cross-shard combine --
    m_g = jax.lax.pmax(m_loc, AXIS)
    scale = jnp.exp(m_loc - m_g)
    l_g = jax.lax.psum(l_loc * scale, AXIS)
    acc_g = jax.lax.psum(acc_loc * scale[..., None], AXIS)
    l_g = jnp.where(l_g == 0.0, 1.0, l_g)
    out = (acc_g / l_g[..., None]).reshape(B, Hq, Tq, D).astype(q.dtype)
    return out, ck, cv, cpos


def sharded_decode_attention(
    mesh: Mesh,
    q: jax.Array,              # (B, Hq, 1, D)
    cache: Dict,               # {"k","v","pos"} seq-sharded over AXIS
    k_new: jax.Array,          # (B, Hkv, 1, D)
    v_new: jax.Array,
    positions: jax.Array,      # (1,) absolute position
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    dp_axes: Tuple[str, ...] = ("pod", "data"),
) -> Tuple[jax.Array, Dict]:
    dp = tuple(a for a in dp_axes if a in mesh.axis_names)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    n_shards = mesh.shape[AXIS]
    fn = shard_map(
        lambda q_, ck_, cv_, cp_, kn_, vn_, pos_: _local_step(
            q_, ck_, cv_, cp_, kn_, vn_, pos_,
            causal=causal, window=window, softcap=softcap, n_shards=n_shards,
        ),
        mesh=mesh,
        in_specs=(
            P(dp_spec, None, None, None),      # q replicated over model
            P(dp_spec, None, AXIS, None),      # cache k: seq sharded
            P(dp_spec, None, AXIS, None),      # cache v
            P(AXIS),                           # cache positions
            P(dp_spec, None, None, None),      # new k
            P(dp_spec, None, None, None),      # new v
            P(None),                           # position scalar-vector
        ),
        out_specs=(
            P(dp_spec, None, None, None),
            P(dp_spec, None, AXIS, None),
            P(dp_spec, None, AXIS, None),
            P(AXIS),
        ),
        check_vma=False,
    )
    out, ck, cv, cpos = fn(q, cache["k"], cache["v"], cache["pos"],
                           k_new, v_new, positions)
    return out, {"k": ck, "v": cv, "pos": cpos}
