"""Seconds a save spends cutting dirty leaves into page-aligned runs (program
spans ``ckpt.pack``: byte copies, padding, per-page digest tuples), summed
per save, mean over the window's saves."""

from bench.progspans import seconds_per


def read(run):
    return seconds_per(run, "ckpt_save", "ckpt.save", "ckpt.pack")
