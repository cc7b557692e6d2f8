"""Client-side latency arithmetic.

A request that failed (no answer, an error, or still open when the drain
cap ran out) counts as missing every latency limit: it enters a
percentile as +infinity.
"""

from __future__ import annotations

import math

INF = math.inf


def percentile(values, q: float) -> float:
    """Linear-interpolated ``q``-th percentile (NumPy's default rule);
    infinities sort last, and a rank that touches one gives +infinity."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    rank = q / 100.0 * (len(xs) - 1)
    lo, hi = math.floor(rank), math.ceil(rank)
    if math.isinf(xs[hi]):
        return INF
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def ttft_s(rec) -> float:
    """Due time to the first streamed content delta; +inf if failed."""
    if not rec.ok or rec.t_first is None:
        return INF
    return rec.t_first - rec.due


def tpot_ms(rec):
    """(last delta - first delta) / (tokens - 1) in ms, for a completed
    request of two tokens or more; None otherwise."""
    if not rec.ok or rec.n_tokens < 2:
        return None
    return 1e3 * (rec.t_last - rec.t_first) / (rec.n_tokens - 1)


def median(values) -> float:
    return percentile(values, 50.0)
