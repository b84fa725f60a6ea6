"""Seconds a save's ``write_many`` spends storing full pages through the
provider manager (program spans ``blob.store_pages`` under ``ckpt.save``),
mean over the window's saves."""

from bench.progspans import seconds_per


def read(run):
    return seconds_per(run, "ckpt_save", "ckpt.save", "blob.store_pages")
