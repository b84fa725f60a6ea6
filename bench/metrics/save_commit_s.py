"""Seconds a save spends on its manifest and commit pointer (program span
``ckpt.commit``: JSON, zlib, the two writes and syncs, the pin), mean over
the window's saves."""

from bench.progspans import seconds_per


def read(run):
    return seconds_per(run, "ckpt_save", "ckpt.save", "ckpt.commit")
