"""The page-digest kernel's share of its roofline, in percent.

It reads every page's u32 words and writes 8 bytes a page, once per leaf
per save: those bytes over the chip's HBM bandwidth, over the kernel's
summed device time in the trace.  The kernel is memory-bound (a few
integer operations per word), so bandwidth is its roofline.
"""

import re

import numpy as np

from bench.flops import digest_bytes
from bench.gen import leaves_with_paths

KERNEL = re.compile(r"^page_digest_pallas(\.\d+)?$")   # the Mosaic kernel's op


def read(run):
    t, saves = run.trace, run.job.saves
    if t is None or not saves or run.peak is None:
        return None
    kernel_s = t.op_time(KERNEL)
    if not kernel_s:
        return None
    sizes = [int(np.prod(s.shape)) * s.dtype.itemsize
             for _, s in leaves_with_paths(run.job.sys.abstract)]
    moved = digest_bytes(sizes, run.cell.cfg["store"]["page_bytes"]) * len(saves)
    return 100.0 * moved / run.peak.hbm_bytes_s / kernel_s
