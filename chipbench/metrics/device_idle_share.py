"""Device: the share of the traced window in which no op ran on the chip,
1 - (union of device op intervals) / window, in %.  Moves
``tpot_p90_ms``."""


def read(run):
    if run.reduced is None:
        return None
    r = run.reduced
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
