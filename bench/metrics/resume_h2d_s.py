"""Seconds a resume spends putting the restored state on the device with
its shardings, until the copy is done, mean over the window's resumes."""

from statistics import fmean


def read(run):
    d = run.spans.durations("resume_h2d")
    return fmean(d) if d else None
