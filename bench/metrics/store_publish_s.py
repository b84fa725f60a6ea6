"""Seconds a save's ``write_many`` spends publishing: version assignment,
boundary pages, the metadata weave and completion (program spans
``blob.publish`` under ``ckpt.save``), mean over the window's saves."""

from bench.progspans import seconds_per


def read(run):
    return seconds_per(run, "ckpt_save", "ckpt.save", "blob.publish")
