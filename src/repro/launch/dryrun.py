import os
# a CPU tool: 512 virtual host devices, and never the chip, so that a
# parent process that holds the chip can still run it
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent on the
production mesh (16x16 single-pod, 2x16x16 multi-pod) and extracts the
raw material for EXPERIMENTS.md:

* ``compiled.memory_analysis()``  — fits-in-HBM evidence;
* ``compiled.cost_analysis()``    — per-device HLO FLOPs / bytes;
* optimized HLO text              — collective payload bytes.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun                  # everything
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi --strategy tp_fsdp
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, applicable
from repro.launch import hlo as H
from repro.launch.mesh import make_production_mesh


def run_cell(arch: str, shape_name: str, mesh_kind: str, strategy: str,
             out_dir: str, remat: str = "full", accum=None,
             tag_suffix: str = "") -> dict:
    from repro.launch.specs import build_cell  # after XLA_FLAGS

    cfg = get_config(arch)
    cell = SHAPES[shape_name]
    ok, why = applicable(cfg, cell)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": why}
    if strategy == "auto":
        # training wants ZeRO-3 (params would not fit replicated across DP);
        # serving keeps params TP-sharded and resident (an FSDP all-gather
        # per decoded token would drown the step in collectives) and shards
        # the KV cache over kv_heads or, failing divisibility, seq
        strategy = "tp_fsdp" if cell.step == "train" else "tp_serve"
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "strategy": strategy, "remat": remat}
    try:
        prog = build_cell(cfg, cell, mesh, strategy=strategy,
                          remat_policy=remat, accum=accum)
        lowered = prog.jitted().lower(*prog.abstract_args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        txt = compiled.as_text()
        coll = H.collective_stats(txt)
        n_chips = mesh.size

        # Roofline terms come from the analytic cost model (mirrors the
        # implementation; XLA cost_analysis counts scan bodies once and
        # is kept as a diagnostic — see launch/costmodel.py docstring).
        from repro.launch.costmodel import cell_costs
        costs = cell_costs(cfg, cell, mesh, strategy, remat, prog.accum)
        roof = H.Roofline(
            flops=costs.flops_per_device,
            hbm_bytes=costs.hbm_bytes_per_device,
            collective_bytes=costs.collective_bytes_per_device,
            n_chips=n_chips,
            model_flops=prog.model_flops,
        )
        rec.update({
            "status": "ok",
            "accum": prog.accum,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            },
            "cost_hlo_raw": {k: float(v) for k, v in ca.items()
                             if isinstance(v, (int, float))},
            "collectives_hlo": {
                "bytes_by_op": coll.bytes_by_op,
                "count_by_op": coll.count_by_op,
                "note": "per-op payloads with scan bodies counted once",
            },
            "analytic_breakdown": {k: float(v) for k, v in costs.breakdown.items()},
            "analytic_notes": costs.notes,
            "roofline": roof.as_dict(),
        })
        print(f"[ok] {arch} {shape_name} {mesh_kind} {strategy}: "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s "
              f"bottleneck={roof.bottleneck} step={roof.step_time_s*1e3:.2f}ms "
              f"mfu_bound={roof.mfu_bound if roof.mfu_bound is None else round(roof.mfu_bound,3)}")
    except Exception as e:  # a failure here is a bug in the system
        rec.update({"status": "fail", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
        print(f"[FAIL] {arch} {shape_name} {mesh_kind} {strategy}: {e}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{mesh_kind}_{strategy}{tag_suffix}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=2, default=str)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default=None, choices=["single", "multi", None])
    ap.add_argument("--strategy", default="auto")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--tag-suffix", default="")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [args.mesh] if args.mesh else ["single", "multi"]

    results = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                results.append(run_cell(arch, shape, mesh_kind, args.strategy,
                                        args.out, args.remat, args.accum,
                                        args.tag_suffix))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_fail = sum(r["status"] == "fail" for r in results)
    print(f"\ndry-run: {n_ok} ok / {n_skip} skipped / {n_fail} FAILED "
          f"of {len(results)} cells")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
