"""Bytes each save's pack phase still copied: the dirty runs that pass their
leaf's end and are zero-padded (``CheckpointStats.pack_copy_bytes``), mean
over the saves of the window."""

from statistics import fmean


def read(run):
    counts = [getattr(s, "pack_copy_bytes", None) for s in run.job.saves]
    if not counts or None in counts:
        return None
    return fmean(counts)
