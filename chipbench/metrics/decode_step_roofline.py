"""Model (``models/``): the decode steps' share of their roofline, in %.

For each decode segment traced whole inside the window, the least time of
each of its steps, the larger of its operations over peak FLOP/s and its
bytes over peak HBM bandwidth (``roofline.decode_step``: every weight
once, plus the K/V of the positions that step's row attends), summed,
over the device time of the segment's programs.  Moves ``tpot_p90_ms``.
"""

from chipbench import roofline
from chipbench.trace_reduce import device_time_by_mark


def read(run):
    if run.trace is None or run.peak is None:
        return None
    least = device = 0.0
    for mark, ns in device_time_by_mark(
            run.trace, ("chipbench.prefill", "chipbench.segment")):
        if mark.name != "chipbench.segment" or not mark.args["steps"]:
            continue
        base = mark.args["plen"] + 1 + mark.args["first_step"]
        for j in range(mark.args["steps"]):
            least += roofline.least_time(
                *roofline.decode_step(run.config, [base + j]), run.peak)
        device += ns / 1e9
    return 100.0 * least / device if device else None
