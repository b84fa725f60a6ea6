"""Seconds a save spends copying dirty leaves from the device (program spans
``ckpt.d2h``), summed per save, mean over the window's saves."""

from bench.progspans import seconds_per


def read(run):
    return seconds_per(run, "ckpt_save", "ckpt.save", "ckpt.d2h")
