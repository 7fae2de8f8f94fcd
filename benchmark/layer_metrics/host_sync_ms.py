"""host_sync_ms — layer: engine scheduler (llm/engine.py `_run_scheduler`).

Median duration of the `engine.host_sync` spans that start inside the window,
in ms: the blocking read of the oldest chunk's tokens, once per scheduler
iteration. While the device has work in flight this is mostly waiting for
the device, so it follows the chunk time; what it adds beyond that is the
host's."""

from benchmark import spans as sp, stats


def read(run: dict):
    lo, hi = run["window_wall"]
    durs = [(s["b"] - s["a"]) * 1000.0
            for s in sp.named(run["spans"], "engine.host_sync", lo, hi)]
    return stats.percentile(durs, 50) if durs else None
