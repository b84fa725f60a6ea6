"""Seconds of one resume, mean over the window's resumes: from dropping the
device state to the first step's loss on the host (the restore, the copy to
the device, the digest cache, the reader, one train step)."""

from statistics import fmean


def read(run):
    d = run.spans.durations("resume")
    return fmean(d) if d else None
