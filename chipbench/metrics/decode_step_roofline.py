"""Model (``models/``): the decode steps' share of their roofline, in %.

For each decode segment traced whole inside the window, the least time of
each of its steps, the larger of its operations over peak FLOP/s and its
bytes over peak HBM bandwidth (``roofline.decode_steps``, the family's
counts), summed, over the device time of the segment's programs.  Moves
``tpot_p90_ms``.
"""

from chipbench import roofline
from chipbench.trace_reduce import device_time_by_mark


def read(run):
    if run.trace is None or run.peak is None:
        return None
    least = device = 0.0
    for mark, ns in device_time_by_mark(
            run.trace, ("chipbench.prefill", "chipbench.segment")):
        if mark.name != "chipbench.segment":
            continue
        steps = roofline.decode_steps(run.config, mark.args)
        if not steps:
            continue
        for flops, nbytes in steps:
            least += roofline.least_time(flops, nbytes, run.peak)
        device += ns / 1e9
    return 100.0 * least / device if device else None
