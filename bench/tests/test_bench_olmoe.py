"""OLMoE's share through the program against the plain reference.

At a small size (d 64, 4 heads, 8 experts, top-2, shares of 2 or 4
experts), in float32 on the CPU: the program's loss and every gradient
leaf against ``bench.families.olmoe.loss_fn``.  The same comparison holds
when the router sends every token to one held expert (nothing is
dropped), and fails for three variants of the layer the reference does
not describe: renormalised top-k weights, a per-head QK-norm, and a
GShard layer that drops the pairs past a capacity of 1.25.

Tolerance: both sides compute in float32, the program with its sorted
grouped products and the reference densely over the held experts, so the
same sums run in another order.  Their relative gaps read about 1e-6; a
gap is allowed 1e-4 of the leaf's largest gradient, and each variant
reads above 1e-2.
"""

from __future__ import annotations

import copy
import dataclasses
import functools
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import gen, reference
from bench.families import olmoe
from repro.models import build_model
from repro.models import moe as M

FILE = Path(__file__).resolve().parents[1] / "configs" / "olmoe1b7b-l4e8.train-state.json"
TOL = 1e-4


def small_cfg(held: int = 4, offset: int = 0) -> dict:
    cfg = json.loads(FILE.read_text())
    cfg.update(hidden_size=64, intermediate_size=32, num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=4, vocab_size=64, num_experts=held, expert_offset=offset,
               num_experts_per_tok=2, dtype="float32")
    cfg["published"] = dict(cfg["published"], num_experts=8)
    return cfg


def weights(cfg: dict, seed: int = 0) -> dict:
    """Flat ``{path: array}``: N(0, 0.1) weights, norm scales 1 + N(0, 0.1), a
    router wide enough that no two experts tie for a token."""
    out = {}
    for i, (path, (shape, _)) in enumerate(sorted(olmoe.param_shapes(cfg).items())):
        w = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), i), shape) * 0.1
        if "norm" in path:
            w = 1.0 + w
        if path.endswith("router"):
            w = w * 10
        out[path] = w
    return out


def nest(flat: dict, tree):
    return jax.tree_util.tree_map_with_path(lambda p, _: flat[gen.path_str(p)], tree)


def program_loss(cfg: dict, flat: dict, batch, mc=None):
    """The program's loss and gradients, as flat dicts."""
    model = build_model(mc or olmoe.model_config(cfg))
    params = nest(flat, model.abstract()[0])
    (loss, metrics), g = jax.value_and_grad(model.loss_fn, has_aux=True)(params, batch)
    return float(loss), dict(gen.leaves_with_paths(g)), metrics


def reference_loss(cfg: dict, flat: dict, batch):
    fn = functools.partial(olmoe.loss_fn, cfg=cfg, Q=lambda x: x)
    loss, g = jax.value_and_grad(lambda p: fn(p, batch["tokens"], batch["labels"]))(flat)
    return float(loss), g


def batch_for(cfg: dict, seed: int = 1):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (2, 17), 0, cfg["vocab_size"])
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def gap(cfg, flat, batch, mc=None) -> float:
    """The larger of the loss's relative gap and, by the worst leaf, the
    largest gradient difference over the leaf's largest gradient."""
    lp, gp, _ = program_loss(cfg, flat, batch, mc)
    lr, gr = reference_loss(cfg, flat, batch)
    leaves = [float(jnp.max(jnp.abs(gp[k] - gr[k])) / jnp.max(jnp.abs(gr[k]))) for k in gr]
    return max(abs(lp - lr) / abs(lr), *leaves)


@pytest.mark.parametrize("held,offset", [(4, 0), (4, 4), (2, 2), (2, 6)])
def test_program_matches_reference(held, offset):
    cfg = small_cfg(held, offset)
    flat, batch = weights(cfg), batch_for(cfg)
    lp, gp, metrics = program_loss(cfg, flat, batch)
    lr, gr = reference_loss(cfg, flat, batch)
    assert set(gp) == set(gr) == set(olmoe.param_shapes(cfg))
    assert abs(lp - lr) / abs(lr) < TOL
    for k in gr:
        assert gp[k].shape == gr[k].shape, k
        np.testing.assert_allclose(gp[k], gr[k], rtol=0, atol=TOL * float(jnp.max(jnp.abs(gr[k]))),
                                   err_msg=k)
    assert 0 < int(metrics["expert_rows"]) < 2 * 16 * 2 * 2


def imbalanced(cfg: dict, flat: dict, to: int, then: int) -> dict:
    """Weights whose router sends every token to expert ``to`` first and to
    ``then`` second: positive embeddings, attention that adds little, and
    a router that scores only those two experts."""
    flat = dict(flat)
    flat["embed/table"] = jnp.abs(flat["embed/table"]) + 1.0
    flat["groups/0/mixer/wo"] = flat["groups/0/mixer/wo"] * 1e-3
    flat["groups/0/ffn/wo"] = flat["groups/0/ffn/wo"] * 1e-3
    flat["groups/0/norm2/scale"] = jnp.ones_like(flat["groups/0/norm2/scale"])
    router = jnp.zeros_like(flat["groups/0/ffn/router"])
    flat["groups/0/ffn/router"] = router.at[..., to].set(1.0).at[..., then].set(0.5)
    return flat


def test_dropless_under_forced_imbalance():
    cfg = small_cfg(4, 4)
    flat, batch = imbalanced(cfg, weights(cfg), to=5, then=1), batch_for(cfg)
    _, _, metrics = program_loss(cfg, flat, batch)
    assert int(metrics["expert_rows"]) == 2 * 16 * 2    # every token of both layers, one expert
    assert gap(cfg, flat, batch) < TOL


def test_renormalised_top_k_fails():
    cfg = small_cfg()
    flat, batch = weights(cfg), batch_for(cfg)
    mc = dataclasses.replace(olmoe.model_config(cfg), norm_topk_prob=True)
    assert gap(cfg, flat, batch, mc) > 100 * TOL


def test_per_head_qk_norm_fails():
    cfg = small_cfg()
    flat, batch = weights(cfg), batch_for(cfg)
    mc = dataclasses.replace(olmoe.model_config(cfg), qk_norm_width="head")
    # the per-head scale is one head's row of the full-width one, which the
    # reference gets for every head
    per_head = dict(flat)
    for which in ("q", "k"):
        full = flat[f"groups/0/mixer/{which}_norm"]
        per_head.pop(f"groups/0/mixer/{which}_norm")
        per_head[f"groups/0/mixer/{which}_scale"] = full[:, 0]
        flat[f"groups/0/mixer/{which}_norm"] = jnp.broadcast_to(full[:, :1], full.shape)
    lp, gp, _ = program_loss(cfg, per_head, batch, mc)
    lr, gr = reference_loss(cfg, flat, batch)
    wq = "groups/0/mixer/wq"
    worst = float(jnp.max(jnp.abs(gp[wq] - gr[wq])) / jnp.max(jnp.abs(gr[wq])))
    assert max(abs(lp - lr) / abs(lr), worst) > 100 * TOL


def test_capacity_layer_fails(monkeypatch):
    """A GShard layer: each expert keeps its first 1.25·N·k/E pairs and
    drops the rest.  Under the imbalance above it drops most of them."""
    cfg = small_cfg(4, 4)
    flat, batch = imbalanced(cfg, weights(cfg), to=5, then=1), batch_for(cfg)
    real = M.kops.grouped_matmul

    def capped(lhs, rhs, sizes):
        capacity = int(1.25 * lhs.shape[0] / 8)          # rows are N·k, 8 experts
        ends = jnp.cumsum(sizes)
        row = jnp.arange(lhs.shape[0])
        group = jnp.searchsorted(ends, row, side="right")
        start = (ends - sizes)[jnp.minimum(group, len(sizes) - 1)]
        return real(jnp.where((row - start < capacity)[:, None], lhs, 0), rhs, sizes)

    monkeypatch.setattr(M.kops, "grouped_matmul", capped)
    assert gap(cfg, flat, batch) > 100 * TOL


def test_model_config_refuses_what_the_program_runs_otherwise():
    cfg = small_cfg()
    olmoe.model_config(cfg)
    for key, value, named in (("norm_topk_prob", True, "norm_topk_prob"),
                              ("tie_word_embeddings", True, "tie_embeddings"),
                              ("rms_norm_eps", 1e-6, "norm_eps"),
                              ("router_aux_loss_coef", 0.001, "router_aux_loss_coef"),
                              ("z_loss", 0.0, "z_loss")):
        with pytest.raises(ValueError, match=named):
            olmoe.model_config(dict(cfg, **{key: value}))


def test_shapes_and_counts_match_the_program():
    cfg = json.loads(FILE.read_text())
    model = build_model(olmoe.model_config(cfg))
    shapes = {p: (tuple(s.shape), s.dtype) for p, s in gen.leaves_with_paths(model.abstract()[0])}
    assert shapes == {p: (s, jnp.dtype(d)) for p, (s, d) in olmoe.param_shapes(cfg).items()}
    assert olmoe.n_params(cfg) == sum(int(np.prod(s)) for s, _ in shapes.values()) == 294_750_208
    # the state: bf16 weights and f32 master, m and v; f32 router and norm scales
    state = sum(int(np.prod(s)) * (4 * 3 + jnp.dtype(d).itemsize) for s, d in shapes.values())
    assert state + 8 == 4_127_621_128
    # by hand: attention 4·2048² + QK-norm 2·2048 a layer; one expert of
    # 3·2048·1024 a token (8 of 64 held, top-8); router, norms, the head;
    # 12·L·d·T of attention scores
    d, L, T = 2048, 4, 4096
    active = L * (4 * d * d + 2 * d + d * 64 + 2 * d + 3 * d * 1024) + d * 6288 + d
    assert olmoe.train_flops_per_token(cfg, T) == 6.0 * active + 12.0 * L * d * T
    assert olmoe.expert_gmm_flops(cfg, 10) == 10 * 9 * 2 * d * 1024


def test_fp8_control_moves_the_reference():
    """The control the limits are set against runs on this family."""
    cfg = copy.deepcopy(small_cfg())
    seed = 2**33 + 3
    stream = gen.corpus(seed, 4096, cfg["vocab_size"])
    cfg.update(batch=2, seq=16)
    batches = gen.batches(stream, 2, 16, 2)
    f32 = reference.train_numbers(cfg, seed, batches)
    fp8 = reference.train_numbers(cfg, seed, batches, "fp8")
    assert f32["losses"] != fp8["losses"]
