"""Per-arch smoke tests (reduced configs) + cache-consistency properties."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, applicable
from repro.models import build_model
from repro.models import lm as LM

RNG = jax.random.PRNGKey(0)


def _batch_for(cfg, B, T):
    batch = {
        "tokens": jax.random.randint(RNG, (B, T), 0, cfg.vocab_size),
        "labels": jax.random.randint(RNG, (B, T), 0, cfg.vocab_size),
    }
    if cfg.frontend == "vision_stub":
        batch["vision_embeds"] = 0.01 * jnp.ones(
            (B, cfg.n_frontend_tokens, cfg.d_model), jnp.float32)
    if cfg.arch_kind == "encdec":
        batch["enc_embeds"] = 0.01 * jnp.ones((B, T, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward_and_train_step(arch):
    """Reduced config: one forward/train step, output shapes, no NaNs."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params, axes = model.init(RNG)
    # axes tree mirrors params tree (axes leaves are name tuples)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    # every leaf's rank matches its axes names
    jax.tree.map(lambda p, a: None if p.ndim == len(a) else 1 / 0, params,
                 jax.tree.map(lambda x: x, axes,
                              is_leaf=lambda x: isinstance(x, tuple)))
    B, T = 2, 16
    batch = _batch_for(cfg, B, T)
    loss, metrics = jax.jit(lambda p, b: model.loss_fn(p, b))(params, batch)
    assert loss.shape == ()
    assert jnp.isfinite(loss)
    assert metrics["tokens"] == B * T
    # gradients exist + finite
    g = jax.grad(lambda p: model.loss_fn(p, batch)[0])(params)
    gleaves = jax.tree.leaves(g)
    assert all(jnp.isfinite(x).all() for x in gleaves)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_serve(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params, _ = model.init(RNG)
    B, T = 2, 8
    batch = _batch_for(cfg, B, T)
    batch.pop("labels")
    cache = model.init_cache(B, max_len=24)
    if cfg.arch_kind == "encdec":
        logits, cache, mem = model.prefill(params, batch, cache)
        step2 = model.decode_step(params, jnp.argmax(logits, -1).astype(jnp.int32),
                                  jnp.asarray(T), cache, mem)
    else:
        logits, cache = model.prefill(params, batch, cache)
        step2 = model.decode_step(params, jnp.argmax(logits, -1).astype(jnp.int32),
                                  jnp.asarray(T), cache)
    logits2 = step2[0]
    assert logits.shape == (B, cfg.vocab_size)
    assert logits2.shape == (B, cfg.vocab_size)
    assert jnp.isfinite(logits).all() and jnp.isfinite(logits2).all()


@pytest.mark.parametrize("arch", ["olmo-1b", "h2o-danube-3-4b",
                                  "recurrentgemma-2b", "xlstm-350m"])
def test_decode_matches_teacher_forcing(arch):
    """Cached decode must reproduce the full-forward logits."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(1))
    B, T, T0 = 2, 12, 6
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab_size)
    x = params["embed"]["table"][toks]
    full, _ = LM.apply_stack_train(params, cfg, x, jnp.arange(T))
    full_logits = LM._logits(params, cfg, full)
    cache = model.init_cache(B, max_len=T + 4)
    lg, cache = model.prefill(params, {"tokens": toks[:, :T0]}, cache)
    errs = [float(jnp.max(jnp.abs(lg - full_logits[:, T0 - 1])))]
    for t in range(T0, T):
        lg, cache = model.decode_step(params, toks[:, t], jnp.asarray(t), cache)
        errs.append(float(jnp.max(jnp.abs(lg - full_logits[:, t]))))
    assert max(errs) < 2e-2, errs


def test_remat_policies_agree():
    cfg = get_config("olmo-1b").reduced()
    model = build_model(cfg)
    params, _ = model.init(RNG)
    batch = _batch_for(cfg, 2, 16)
    l_none = model.loss_fn(params, batch, "none")[0]
    l_full = model.loss_fn(params, batch, "full")[0]
    l_dots = model.loss_fn(params, batch, "dots")[0]
    np.testing.assert_allclose(l_none, l_full, rtol=1e-6)
    np.testing.assert_allclose(l_none, l_dots, rtol=1e-6)
    # gradients agree too
    g1 = jax.grad(lambda p: model.loss_fn(p, batch, "none")[0])(params)
    g2 = jax.grad(lambda p: model.loss_fn(p, batch, "full")[0])(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)


def test_moe_load_balance_aux_in_metrics():
    cfg = get_config("olmoe-1b-7b").reduced()
    model = build_model(cfg)
    params, _ = model.init(RNG)
    batch = _batch_for(cfg, 2, 16)
    loss, metrics = model.loss_fn(params, batch)
    assert metrics["aux"] > 0.0


def test_param_count_analytics_roughly_match():
    for arch in ["olmo-1b", "qwen3-32b", "olmoe-1b-7b"]:
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        params, _ = model.init(RNG)
        real = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        approx = cfg.param_count()
        assert 0.5 < approx / real < 2.0, (arch, approx, real)


def test_long_500k_applicability_flags():
    sub = {a: get_config(a).sub_quadratic for a in ARCH_IDS}
    assert sub["recurrentgemma-2b"] and sub["xlstm-350m"] and sub["h2o-danube-3-4b"]
    assert not sub["qwen3-32b"] and not sub["internvl2-76b"]
    cell = SHAPES["long_500k"]
    ok, why = applicable(get_config("qwen3-32b"), cell)
    assert not ok and "quadratic" in why


def _old_head_rmsnorm(x, scale):
    """The per-head QK-norm as qwen3's path computed it before the norm eps
    and the full-width QK-norm became configurable."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
    return (xf * scale).astype(x.dtype)


@pytest.mark.parametrize("arch", ["qwen3-32b", "olmo-1b"])
def test_attention_and_norms_bit_identical_to_their_fixed_eps_form(arch):
    """qwen3's per-head QK-norm and RMSNorm and OLMo's LayerNorm keep their
    1e-6 and 1e-5 when the configuration states no eps: bit for bit."""
    from repro.models import layers as Ly
    cfg = get_config(arch).reduced(dtype="bfloat16")
    model = build_model(cfg)
    params, _ = model.init(RNG)
    blk = jax.tree.map(lambda a: a[0], params["groups"][0])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, cfg.d_model)).astype(jnp.bfloat16)
    q, k, _ = Ly._project_qkv(blk["mixer"], cfg, x, jnp.arange(8))
    wq = jnp.einsum("btd,dhk->bhtk", x, blk["mixer"]["wq"])
    wk = jnp.einsum("btd,dhk->bhtk", x, blk["mixer"]["wk"])
    if cfg.qk_norm:
        wq = _old_head_rmsnorm(wq, blk["mixer"]["q_scale"])
        wk = _old_head_rmsnorm(wk, blk["mixer"]["k_scale"])
    np.testing.assert_array_equal(q, Ly.rope(wq, jnp.arange(8), cfg.rope_theta))
    np.testing.assert_array_equal(k, Ly.rope(wk, jnp.arange(8), cfg.rope_theta))
    xf = x.astype(jnp.float32)
    if cfg.norm_kind == "rmsnorm":
        old = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6) * blk["norm1"]["scale"]
    else:
        mean = jnp.mean(xf, -1, keepdims=True)
        old = (xf - mean) * jax.lax.rsqrt(jnp.mean((xf - mean) ** 2, -1, keepdims=True) + 1e-5)
    np.testing.assert_array_equal(Ly.apply_norm(blk["norm1"], cfg, x), old.astype(x.dtype))
