"""The arithmetic that turns request timings into end-to-end metrics. Kept
with the benchmark so that no later PR can change how a number is taken."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks, as numpy's default does. Raises on an empty sample: a
    metric without samples is left out, not reported as 0."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def tpot_ms(t_first: float, t_last: float, n_tokens: int) -> float | None:
    """Time per output token of one request, in ms: (last token time - first
    token time) / (tokens - 1). The engine emits tokens in chunks of up to
    16, so the gap between two single tokens is not what a user of this
    server sees; the pace of the whole answer is. None for one token."""
    if n_tokens < 2:
        return None
    return (t_last - t_first) * 1000.0 / (n_tokens - 1)


def with_missed(latencies_ms, n_missed: int):
    """An open loop's latency sample: a request that failed, was shed or
    never finished counts as having missed every limit, so it enters the
    sample as +inf and pushes the tail up instead of vanishing from it."""
    return list(latencies_ms) + [math.inf] * n_missed


def ttft_sample_ms(records) -> list[float]:
    """Times from due to first token of an open loop's requests (records
    of the driver: `due`, `t_first`, `ok`), a missed one as +inf. Empty for
    a closed loop, which has no due time."""
    window = [r for r in records if r.due is not None]
    done = [(r.t_first - r.due) * 1000.0 for r in window if r.ok]
    return with_missed(done, len(window) - len(done))


def summary(values) -> dict:
    """Median, count, and the highest percentile that has at least ten
    samples beyond it (`choosing-metrics`, section 1)."""
    xs = [v for v in values]
    n = len(xs)
    if not n:
        return {"n": 0}
    out = {"n": n, "p50": percentile(xs, 50)}
    for q in (99, 95, 90):
        if n * (100 - q) / 100.0 >= 10:
            out["highest_supported"] = f"p{q}"
            break
    for q in (90, 95, 99):
        out[f"p{q}"] = percentile(xs, q)
    return out
