"""Model (``models/``): the prefill's share of its roofline, in %.  For
each ``chipbench.prefill`` mark traced whole inside the window, the least
time of prefilling its prompt at the bucket length the program ran it at
(the ``bucket`` its ``prefill`` region recorded for a prompt of that
many tokens; the larger of the operations over peak FLOP/s and the bytes
over peak HBM bandwidth, ``prefill_cost`` of the configuration's family)
over the device time of the mark's programs; the median over the marks.
Moves ``ttft_short_p50_s``.  Nothing is read for a family without
``prefill_cost``, nor where the regions carry no ``bucket``."""

from chipbench import families, roofline
from chipbench.stats import median
from chipbench.trace_reduce import device_time_by_mark


def read(run):
    cost = getattr(families.of(run.config), "prefill_cost", None)
    if run.trace is None or run.peak is None or cost is None:
        return None
    buckets = {s.args["tokens"]: s.args["bucket"] for s in run.spans
               if s.name == "prefill" and s.args and "bucket" in s.args}
    shares = [100.0 * roofline.least_time(
                  *cost(run.config, {**mark.args,
                                     "bucket": buckets[mark.args["tokens"]]}),
                  run.peak) / (ns / 1e9)
              for mark, ns in device_time_by_mark(
                  run.trace, ("chipbench.prefill", "chipbench.segment"))
              if mark.name == "chipbench.prefill" and ns > 0
              and mark.args.get("tokens") in buckets]
    return median(shares) if shares else None
