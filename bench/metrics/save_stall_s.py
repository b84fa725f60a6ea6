"""Seconds the loop is blocked in ``ckpt.save``, per save completed."""

from statistics import fmean


def read(run):
    d = run.spans.durations("ckpt_save")
    return fmean(d) if d else None
