"""Bytes each save wrote, as the program counts them
(``CheckpointStats.written_bytes``), mean over the saves of the window."""

from statistics import fmean


def read(run):
    saves = run.job.saves
    return fmean(s.written_bytes for s in saves) if saves else None
