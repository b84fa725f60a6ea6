"""Seconds of one garbage-collection round over the deployment, mean over rounds."""

from statistics import fmean


def read(run):
    d = run.spans.durations("gc_round")
    return fmean(d) if d else None
