"""The arithmetic of the end-to-end metrics, free of JAX so it is testable alone.

Every function takes plain lists of host-clock seconds and returns a number
as measured; nothing is rounded or clamped.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default).  Raises on an empty list: a tail of
    nothing is not 0."""
    if not values:
        raise ValueError("percentile of an empty list")
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def block_median_rate(block_seconds: Sequence[float], units_per_block: float,
                      chips: int = 1) -> float:
    """Units per second per chip at the median block time: what the steps
    cost when nothing stalls.  A per-layer reading only — a stalled block
    moves the median by at most one rank, so it cannot be the end-to-end
    rate; where it parts from ``total_rate`` a block stalled."""
    if not block_seconds:
        raise ValueError("no block was timed")
    return units_per_block / statistics.median(block_seconds) / chips


def total_rate(block_seconds: Sequence[float], units_per_block: float,
               chips: int = 1) -> float:
    """The end-to-end rate: every block's units over the exact time from the
    window's start to its last block boundary, so a stall anywhere counts
    and no step is cut by a nominal window edge."""
    return units_per_block * len(block_seconds) / sum(block_seconds) / chips


def spread(values: Sequence[float]) -> float:
    """Inter-quartile distance over the median, quartiles as
    ``statistics.quantiles(values, n=4)`` gives them (the contract's rule
    for a bound)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def request_latencies(requests: Sequence[Dict[str, object]], t0: float,
                      t_end: float, ttft_share: float = 0.8,
                      tpot_min_gaps: int = 16) -> Dict[str, object]:
    """TTFT and TPOT samples of an open-loop window ``[t0, t_end]``.

    Each request is a dict with ``due`` (when the schedule said to send
    it), ``first`` and ``last`` (host time of its first and last token, or
    None), ``tokens`` (tokens received), ``want`` (tokens asked for) and
    ``deliveries``: a list of ``(time, n)``, one per ``step()`` return that
    gave it ``n`` tokens, in order.

    * TTFT = first - due, over requests due in the first ``ttft_share`` of
      the window.  Timed from *due*, not from when the generator got round
      to sending, so a stall's cost to later arrivals counts.  One with no
      first token by ``t_end`` is a miss: it has no sample and counts as
      failed.  The generator reports their mean (every request's wait
      weighs the same; the median of ~90 samples moves by 5 % with where
      arrivals fall against step boundaries, the mean by 1-2 %) and keeps
      the median and the p90 for per-layer readings.
    * TPOT = the mean gap between the tokens a request received *inside the
      window*: (its last delivery in the window - its first in the window)
      / (tokens delivered in the window after that first delivery), for
      every request with at least ``tpot_min_gaps`` such gaps, whether it
      began before the window, or finishes after it, or neither.  A request
      censored by either edge is a sample like any other, so the longest
      answers are not left out (they outlast a window) and every step of
      the window weighs on some request.
    * ``tpot_finished`` = (last - first) / (tokens - 1) over the requests
      that finished inside the window: PR 23's definition, printed beside
      the other and bound to nothing.
    """
    ttft: List[float] = []
    tpot: List[float] = []
    tpot_finished: List[float] = []
    missed = 0
    horizon = t0 + ttft_share * (t_end - t0)
    for r in requests:
        due = r["due"]
        if t0 <= due < horizon:
            if r["first"] is not None and r["first"] <= t_end:
                ttft.append(r["first"] - due)
            else:
                missed += 1
        inside = [(t, n) for t, n in r.get("deliveries", ())
                  if t0 <= t <= t_end and n > 0]
        if inside:
            gaps = sum(n for _, n in inside) - inside[0][1]
            if gaps >= max(1, tpot_min_gaps):
                tpot.append((inside[-1][0] - inside[0][0]) / gaps)
        done = (r["last"] is not None and r["tokens"] >= r["want"]
                and t0 <= r["last"] <= t_end)
        if done and r["tokens"] >= 2:
            tpot_finished.append((r["last"] - r["first"]) / (r["tokens"] - 1))
    return {"ttft": ttft, "tpot": tpot, "tpot_finished": tpot_finished,
            "missed": missed}


def stratified(n: int, inverse_cdf, lo: Optional[float] = None,
               hi: Optional[float] = None) -> List[float]:
    """``n`` values at the mid-quantiles (i + 0.5) / n of a distribution
    given by its inverse CDF, clipped to [lo, hi].  Every seed then draws
    the same multiset and only its order differs, so the seed cannot change
    the amount of work in a run."""
    out = []
    for i in range(n):
        v = inverse_cdf((i + 0.5) / n)
        if lo is not None:
            v = max(lo, v)
        if hi is not None:
            v = min(hi, v)
        out.append(v)
    return out


def lognormal_icdf(median: float, sigma: float):
    nd = statistics.NormalDist()
    return lambda p: median * math.exp(sigma * nd.inv_cdf(p))


def exponential_icdf(mean: float):
    return lambda p: -mean * math.log(1.0 - p)


def uniform_icdf(lo: float, hi: float):
    return lambda p: lo + (hi - lo) * p


def balanced_order(n: int, group: int, rng) -> List[int]:
    """A permutation of range(n) — indices into a *sorted* list — in which
    every consecutive run of ``group`` draws one index from each of
    ``group`` equal strata, in an order the seed shuffles.  Any prefix of the
    resulting sequence then holds nearly the same mix of small and large
    items, whichever seed ordered it."""
    group = max(1, min(group, n))
    strata: List[List[int]] = []
    for g in range(group):
        a, b = g * n // group, (g + 1) * n // group
        idx = list(range(a, b))
        rng.shuffle(idx)
        strata.append(idx)
    out: List[int] = []
    rounds = max(len(s) for s in strata)
    for r in range(rounds):
        row = [s[r] for s in strata if r < len(s)]
        rng.shuffle(row)
        out.extend(row)
    return out
