"""Median seconds of one train step span: batch read, host-to-device copy of
the batch, the jitted step, and the read of its loss."""

from statistics import median


def read(run):
    d = run.spans.durations("train_step")
    return median(d) if d else None
