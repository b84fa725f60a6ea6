"""The expert layer's grouped matrix products' share of their roofline, in percent.

FLOPs: the (token, choice) pairs the program routed to the experts it
holds in the traced steps (the train step's ``expert_rows`` counter,
summed over layers), times the gate, up and down products, each forward
and for both gradients (``expert_gmm_flops`` of the configuration's
family).  Over the chip's bf16 peak times the summed device time of the
``expert_gmm`` and ``expert_tgmm`` kernels in the trace.  At the cell's
widths a product's FLOPs over the peak outweigh its bytes over the HBM
bandwidth, so the compute bound is the roofline.
"""

import re

from bench.families import family

# the Mosaic kernels' ops: ``expert_gmm.12`` forward; under the gradient
# ``transpose_jvp_jit_expert_gmm___.3`` and ``transpose_jvp_jit_expert_tgmm___.3``
KERNEL = re.compile(r"(^|_)expert_t?gmm(___)?(\.\d+)?$")


def read(run):
    t, steps = run.trace, run.job.steps_done
    flops_of = getattr(family(run.cell.cfg), "expert_gmm_flops", None)
    total = getattr(run.job.sys.step_fn, "total", None)
    if t is None or not steps or run.peak is None or flops_of is None or total is None:
        return None
    rows, kernel_s = total("expert_rows", last=steps), t.op_time(KERNEL)
    if not rows or not kernel_s:
        return None
    return 100.0 * flops_of(run.cell.cfg, rows) / (run.peak.bf16_flops * kernel_s)
