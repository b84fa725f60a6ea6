"""Readings from which the limits of ``correct`` are set (not run by the benchmark).

    python bench/control.py --seeds 12 --control-seeds 3 [--out readings.jsonl]

On the chip, at the cells' own sizes, in one process:

* the program's readings: for each seed, the training cell's set-up steps
  through the program (``first_steps``) against the float32 reference;
* the control's: the reference computed in float8 (one scale per tensor)
  put in the program's place, against the float32 reference;
* the faults', planted in the reference put in the program's place: half
  of the batch left out, one input token altered, a step that returns its
  state unchanged;
* the exact comparisons': bytes of the initial weights that differ when
  they are rounded to float8 and back, against their limit of 0.

Each reading is printed as one JSON line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import check, gen, loop, reference, system  # noqa: E402
from bench.run import enable_cache, load_cell  # noqa: E402
from bench.trace import Spans  # noqa: E402

TRAIN = "olmo1b-train.ckpt-every-20"


def emit(out, **rec) -> None:
    line = json.dumps(rec)
    print(line, flush=True)
    if out is not None:
        out.write(line + "\n")
        out.flush()


@jax.jit
def _fp8_bytes_differ(tree):
    """Bytes of the leaves' elements that change when they go through float8."""
    def one(x):
        y = reference._q(x.astype(jnp.float32), "fp8").astype(x.dtype)
        return jnp.sum(x != y) * x.dtype.itemsize
    return sum(one(x) for x in jax.tree.leaves(tree) if jnp.issubdtype(x.dtype, jnp.floating))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_000_000_001)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    enable_cache()
    out = open(args.out, "a") if args.out else None
    readings(load_cell(TRAIN), args.seeds, args.control_seeds, args.first_seed, out)
    return 0


def readings(cell, seeds: int, control_seeds: int, first_seed: int, out=None) -> None:
    cfg, traffic = cell.cfg, cell.traffic
    n_steps = next(op["n"] for op in traffic["setup"] if op["op"] == "first_steps")
    for i in range(seeds):
        seed = first_seed + i
        t0 = time.perf_counter()
        sys_ = system.build(cfg, seed, traffic["corpus_tokens"])
        job = loop.Job(sys_, cfg, traffic, seed, Spans())
        job.op_first_steps(n_steps)
        prog = job.first
        sys_.state = None
        stream = gen.corpus(seed, traffic["corpus_tokens"], cfg["vocab_size"])
        batches = gen.batches(stream, cfg["batch"], cfg["seq"], n_steps)
        ref = reference.train_numbers(cfg, seed, batches)
        emit(out, kind="program", seed=seed, gaps=check.train_gaps(prog, ref),
             losses=prog["losses"], ref_losses=ref["losses"],
             seconds=time.perf_counter() - t0)
        if i < control_seeds:
            for precision, fault in (("fp8", ""), ("f32", "half_batch"), ("f32", "token"),
                                     ("f32", "unchanged")):
                other = reference.train_numbers(cfg, seed, batches, precision, fault)
                emit(out, kind=fault or precision, seed=seed,
                     gaps=check.train_gaps(other, ref))
            params = gen.make_params(seed, sys_.abstract["params"], sys_.shardings["params"])
            emit(out, kind="fp8_exact", seed=seed,
                 readback_bytes_differ_params_only=int(_fp8_bytes_differ(params)))
            del params
        del job, sys_


if __name__ == "__main__":
    sys.exit(main())
