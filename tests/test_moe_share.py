"""The dropless expert layer over a chip's share of the experts.

A share is ``experts_held`` experts from ``expert_offset``: the router
still scores all of them, the share computes its own experts' part.  The
shares of a layer add up to the whole layer, the load-balancing loss is
the same in every share (counted once), and every routed pair is
computed by exactly one share.  The grouped matmul kernel (interpreted
here) agrees with its reference, and the train step keeps a running
count of the pairs its experts computed.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels.grouped_matmul import grouped_matmul_pallas
from repro.kernels.ref import ref_grouped_matmul
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.models import moe as M
from repro.train.optimizer import AdamWConfig
from repro.train.step import TrainStep, TrainStepBuilder

# d 64, 4 heads, 8 experts, top-2
SMALL = dict(d_model=64, n_heads=4, n_kv_heads=4, d_head=16, d_ff=32, vocab_size=64,
             n_experts=8, top_k=2)


def _cfg(**kw):
    return get_config("olmoe-1b-7b").reduced(**SMALL, **kw)


def _layer(cfg, seed=0):
    p = M.init_moe(jax.random.PRNGKey(seed), cfg, jnp.float32)
    p = {k: v[0] for k, v in p.items()}
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 16, cfg.d_model))
    return p, x


@pytest.mark.parametrize("held", [2, 4])
def test_shares_add_up_to_the_whole_layer(held):
    cfg = _cfg()
    p, x = _layer(cfg)
    whole, aux, rows = M.apply_moe(p, cfg, x)
    parts = []
    for off in range(0, cfg.n_experts, held):
        share = dataclasses.replace(cfg, experts_held=held, expert_offset=off)
        ps = dict(p, **{k: p[k][off:off + held] for k in ("wi", "wg", "wo")})
        parts.append(M.apply_moe(ps, share, x))
    # f32 sums of the same products in another order
    np.testing.assert_allclose(sum(o for o, _, _ in parts), whole, rtol=0, atol=1e-6)
    # the load-balancing loss comes from the router over all experts: the
    # same in every share, so the model counts it once
    assert all(float(a) == float(aux) for _, a, _ in parts)
    assert sum(int(r) for _, _, r in parts) == int(rows) == x.shape[0] * x.shape[1] * cfg.top_k


def test_share_of_absent_experts_computes_nothing():
    cfg = _cfg(experts_held=2, expert_offset=6)
    p, x = _layer(cfg)                             # holds experts 6 and 7
    router = p["router"].at[:, 6:].set(-1.0)       # positive inputs never pick 6 or 7
    out, _, rows = M.apply_moe(dict(p, router=router), cfg, jnp.abs(x) + 1.0)
    assert int(rows) == 0
    assert float(jnp.max(jnp.abs(out))) == 0.0


def test_grouped_matmul_kernel_matches_reference():
    """The megablox kernels (interpreted) against ``lax.ragged_dot``: the
    product, both gradients, an empty group and rows past the last group."""
    m, k, n = 512, 256, 128
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs = jax.random.normal(k1, (m, k))
    rhs = jax.random.normal(k2, (4, k, n))
    sizes = jnp.array([100, 0, 250, 60], jnp.int32)     # 410 of 512 rows in groups
    ct = jax.random.normal(k3, (m, n))

    def run(f):
        out = f(lhs, rhs, sizes)
        grads = jax.grad(lambda a, b: jnp.sum(f(a, b, sizes) * ct), argnums=(0, 1))(lhs, rhs)
        return out, grads

    out, (d_lhs, d_rhs) = run(lambda a, b, s: grouped_matmul_pallas(a, b, s, True))
    want, (w_lhs, w_rhs) = run(ref_grouped_matmul)
    # f32 products of 256 terms, accumulated tile by tile
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(d_lhs, w_lhs, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(d_rhs, w_rhs, rtol=1e-5, atol=1e-4)
    assert float(jnp.max(jnp.abs(out[410:]))) == 0.0
    assert float(jnp.max(jnp.abs(d_lhs[410:]))) == 0.0
    assert float(jnp.max(jnp.abs(d_rhs[1]))) == 0.0


def _train_step(cfg):
    model = build_model(cfg)
    b = TrainStepBuilder(model, make_mesh((1, 1), ("data", "model")), strategy="tp",
                         opt=AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10),
                         remat_policy="none", accum=1)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    ap, ax = model.abstract()
    step = b.jit_train_step(ap, ax, jax.eval_shape(lambda: batch))
    return step, b.init_state(jax.random.PRNGKey(0)), batch


def test_train_step_keeps_a_running_count_of_expert_rows(monkeypatch):
    monkeypatch.setattr(TrainStep, "KEEP", 2)        # folds after every 4th step
    step, state, batch = _train_step(_cfg(experts_held=4, expert_offset=4))
    seen = []
    for _ in range(5):
        state, metrics = step(state, batch)
        seen.append(int(metrics["expert_rows"]))
    assert 0 < seen[0] < 2 * 16 * 2 * 2              # some of the pairs of 2 layers
    assert step.total("expert_rows") == sum(seen)
    assert step.total("expert_rows", last=2) == sum(seen[-2:])
    with pytest.raises(ValueError):
        step.total("expert_rows", last=4)


def test_dense_train_step_counts_nothing():
    step, state, batch = _train_step(get_config("olmo-1b").reduced())
    state, metrics = step(state, batch)
    assert "expert_rows" not in metrics
    assert step.total("expert_rows") is None
