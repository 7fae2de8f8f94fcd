"""ttft_p50_ms — layer: proxy, router, replica, engine queue.

Median over the window's requests of the time from the instant a request was
DUE to its first SSE event that carries a token, in ms, on the client's side
(a missed request counts as +inf). It is a user's number, but between two
runs of one seed it moves by 5 to 17% at 0.8 of the knee (PERF.md, PR 24), so
it cannot carry a bound; `latency_per_token_p50_ms` is the bounded metric it moves."""

from benchmark import stats


def read(run: dict):
    sample = stats.ttft_sample_ms(run["records"])
    return stats.percentile(sample, 50) if sample else None
