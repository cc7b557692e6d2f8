"""Model: the whole step's share of the chip's peak, in %: the operations
of every prompt token prefilled and every token decoded by programs traced
whole inside the window (``roofline.prefill``, ``roofline.decode_step``),
over the window's seconds times peak bf16 FLOP/s.  Moves
``tpot_p90_ms``."""

from chipbench import roofline
from chipbench.trace_reduce import device_time_by_mark


def read(run):
    if run.trace is None or run.peak is None:
        return None
    flops = 0.0
    for mark, _ in device_time_by_mark(
            run.trace, ("chipbench.prefill", "chipbench.segment")):
        if mark.name == "chipbench.prefill":
            flops += roofline.prefill(run.config, mark.args["tokens"])
            continue
        base = mark.args["plen"] + 1 + mark.args["first_step"]
        for j in range(mark.args["steps"]):
            flops += roofline.decode_step(run.config, [base + j])[0]
    if not flops:
        return None
    return 100.0 * flops / (run.reduced["window_s"] * run.peak["flops"])
