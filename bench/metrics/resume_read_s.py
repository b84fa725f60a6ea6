"""Seconds a resume spends in ``BlobCheckpointer.restore`` reading the
newest checkpoint back to the host, mean over the window's resumes."""

from statistics import fmean


def read(run):
    d = run.spans.durations("resume_read")
    return fmean(d) if d else None
