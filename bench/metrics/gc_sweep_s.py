"""Seconds of a GC round's sweep phase, the orphan pass included (program
span ``gc.sweep``), mean over the window's rounds."""

from bench.progspans import seconds_per


def read(run):
    return seconds_per(run, "gc_round", "gc.round", "gc.sweep")
