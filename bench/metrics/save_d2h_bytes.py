"""Bytes each save pulled from the device, as the program counts them
(``CheckpointStats.d2h_bytes``), mean over the saves of the window."""

from statistics import fmean


def read(run):
    counts = [getattr(s, "d2h_bytes", None) for s in run.job.saves]
    if not counts or None in counts:
        return None
    return fmean(counts)
