"""Bytes each save's store still copied page by page: the dirty runs not
handed over to it (``CheckpointStats.store_copy_bytes``), mean over the
saves of the window."""

from statistics import fmean


def read(run):
    counts = [getattr(s, "store_copy_bytes", None) for s in run.job.saves]
    if not counts or None in counts:
        return None
    return fmean(counts)
