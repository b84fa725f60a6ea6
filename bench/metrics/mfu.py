"""Model FLOPs of the tokens trained in the window (its family's count, from
shapes) over the window's length times the chip's bf16 peak, in percent.
The window holds whole checkpointing cycles, saves and GC included."""

from bench.families import family


def read(run):
    if not run.job.steps_done or run.peak is None:
        return None
    cfg = run.cell.cfg
    flops = run.job.tokens * family(cfg).train_flops_per_token(cfg, cfg["seq"])
    return 100.0 * flops / (run.window_s * run.peak.bf16_flops * run.cell.chips)
