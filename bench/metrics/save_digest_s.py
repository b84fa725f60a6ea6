"""Seconds a save spends digesting its leaves (program spans ``ckpt.digest``:
the page-digest and delta-mask kernels and the read-back of their results),
summed per save, mean over the window's saves."""

from bench.progspans import seconds_per


def read(run):
    return seconds_per(run, "ckpt_save", "ckpt.save", "ckpt.digest")
