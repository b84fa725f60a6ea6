"""Analytic cost model vs compiled HLO on scan-free probes.

Scan-free = every ``while`` trip count is 1 (single layer group, no grad
accumulation, sequences below the blockwise-attention threshold), where
XLA's once-per-body accounting is exact — validating the analytic
formulas that the roofline table then applies at full trip counts.
"""

import pytest

import jax

from repro.configs import get_config
from repro.configs.shapes import ShapeCell
from repro.launch.costmodel import avg_attended, cell_costs
from repro.launch.mesh import make_mesh
from repro.launch.specs import build_cell

BIG = dict(d_model=512, n_heads=8, n_kv_heads=8, d_head=64, d_ff=1536,
           vocab_size=8192)


def _ratio(arch, step, B, T, overrides, remat="none"):
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_config(arch).reduced(**overrides)
    cell = ShapeCell(f"probe_{step}", step, T, B)
    prog = build_cell(cfg, cell, mesh, strategy="tp", remat_policy=remat, accum=1)
    comp = prog.jitted().lower(*prog.abstract_args).compile()
    hlo = comp.cost_analysis().get("flops", 0.0)
    ana = cell_costs(cfg, cell, mesh, "tp", remat, 1).flops_per_device
    return ana / hlo


@pytest.mark.parametrize(
    "arch,step,B,T,overrides",
    [
        ("olmo-1b", "train", 2, 512, dict(n_layers=1, **BIG)),
        ("olmo-1b", "prefill", 2, 512, dict(n_layers=1, **BIG)),
        ("olmo-1b", "decode", 4, 2048, dict(n_layers=1, **BIG)),
        ("qwen3-32b", "train", 2, 512, dict(n_layers=1, **BIG)),
        ("olmoe-1b-7b", "train", 2, 512,
         dict(n_layers=1, n_experts=8, top_k=2, **BIG)),
        ("recurrentgemma-2b", "train", 2, 256,
         dict(n_layers=3, d_rnn=512, **BIG)),
        ("seamless-m4t-large-v2", "train", 2, 512,
         dict(n_layers=1, n_enc_layers=1, **BIG)),
    ],
)
def test_analytic_flops_close_to_hlo(arch, step, B, T, overrides):
    r = _ratio(arch, step, B, T, overrides)
    assert 0.85 < r < 1.2, f"{arch}/{step}: analytic/HLO = {r:.3f}"


def test_remat_full_multiplier_calibrated():
    r = _ratio("olmo-1b", "train", 2, 512, dict(n_layers=1, **BIG), remat="full")
    assert 0.85 < r < 1.2, r


def test_avg_attended():
    assert avg_attended(8, False, None) == 8
    assert avg_attended(8, True, None) == 4.5
    assert avg_attended(100, True, 10) == pytest.approx(
        (10 * 11 / 2 + 90 * 10) / 100)
    assert avg_attended(8, True, 100) == 4.5


def test_indivisible_heads_are_flagged_as_replicated():
    # qwen1.5: 40 heads on a 16-way model axis -> replicated compute note
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_config("qwen1.5-32b")
    cell = ShapeCell("probe", "train", 128, 8)
    # fake a 16-way model axis via a mesh-shaped query: use spec guard
    from repro.distributed.partitioning import get_rules, spec_for
    import jax as _jax
    # direct check of the shard-factor logic instead (no 256 devices here)
    from repro.launch.costmodel import _div
    assert _div(cfg.n_heads, 16) == 1          # replicated
    assert _div(get_config("qwen3-32b").n_heads, 16) == 16
