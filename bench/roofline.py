"""A kernel's share of its roofline: the least time the chip could take
for the kernel's least work (the larger of operations over peak FLOP/s and
bytes over peak HBM bandwidth), over the kernel's device time in the trace.
The work is a lower bound that ``bench/work/<kernel>.py`` counts from the
graph and the vertices the window updated, so the share cannot pass 100%
however the kernel is implemented."""
from __future__ import annotations


def share(run, pattern: str, kernel: str):
    """Percent of the roofline; None where the trace holds no operation
    matching ``pattern`` or the work count does not apply to the cell."""
    seconds = run.kernel_seconds(pattern)
    if not seconds:
        return None
    work = run.work(kernel)
    if work is None:
        return None
    flops, nbytes = work
    peak = run.peaks()
    least = max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
