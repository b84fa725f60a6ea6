"""A cell of BENCHMARK.json cut to a size the CPU runs in seconds."""

from __future__ import annotations

import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

TINY_MODEL = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=2, num_key_value_heads=2, vocab_size=512, batch=2,
                  seq=32)


def tiny_cell(cell, steps_per_cycle: int = 3):
    """The cell with tiny widths, 1 KiB pages, a small corpus and short cycles."""
    c = copy.deepcopy(cell)
    c.cfg.update(TINY_MODEL)
    c.cfg["store"]["page_bytes"] = 1024
    if "corpus_tokens" in c.traffic:
        c.traffic["corpus_tokens"] = 4096
    c.traffic["cycle"] = [dict(op, n=steps_per_cycle) if op["op"] == "train_steps" else op
                          for op in c.traffic["cycle"]]
    return c


def run_tiny(cell, seed: int = 2**33 + 7, trace: bool = False, seconds: float = 0.5):
    import time
    from bench import run
    return run.run_cell(cell, seed, seconds, trace, None, t_start=time.perf_counter())


def cell_from_files(config: str, traffic: str):
    """A cell made of a configuration file and a traffic file, with no metrics."""
    from bench import run
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    return run.Cell(f"{config}.{traffic}", 1, cfg, mix, [], [])
