"""Seconds of a GC round's mark phase (program span ``gc.mark``), mean over
the window's rounds."""

from bench.progspans import seconds_per


def read(run):
    return seconds_per(run, "gc_round", "gc.round", "gc.mark")
