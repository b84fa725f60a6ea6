"""Share of the ``ckpt.digest`` spans, put on the trace's clock, in which an
operation ran on the device, in percent: the rest is kernel launches,
syncs and read-backs on the host."""

from bench.progspans import busy_share


def read(run):
    return busy_share(run, "ckpt_save", "ckpt.save", "ckpt.digest")
