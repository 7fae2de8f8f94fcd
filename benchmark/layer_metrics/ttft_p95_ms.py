"""ttft_p95_ms — layer: proxy, router, replica, engine queue.

95th percentile of the same sample as `ttft_p50_ms`: the tenth-worst of some
two hundred requests. It follows the few moments in which arrivals bunch
while every slot is taken, so it moves by up to 16% between two runs of one
seed and by 28% across seeds (PERF.md, PR 24): reported, not bounded."""

from benchmark import stats


def read(run: dict):
    sample = stats.ttft_sample_ms(run["records"])
    return stats.percentile(sample, 95) if sample else None
