"""Admission: the ``RankingMonitor``'s pairwise concordance of P(Long)
against observed service over the window's completed requests, in %.
Among shorts alone it is noise, so only the mixed cells list it.  Moves
``ttft_short_p90_s``."""

import math


def read(run):
    c = run.ranking.concordance()
    return None if math.isnan(c) else 100.0 * c
