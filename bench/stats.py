"""Percentiles, copied from the program's `repro.obs.metrics._percentile`
so that no later change to the program moves the yardstick."""

from __future__ import annotations

from typing import Optional


def percentile(values, q: float) -> Optional[float]:
    """Nearest-rank q-th percentile of `values`; None when there are none."""
    xs = sorted(values)
    if not xs:
        return None
    rank = max(0, min(len(xs) - 1, int(round(q / 100.0 * (len(xs) - 1)))))
    return xs[rank]
