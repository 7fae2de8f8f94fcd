"""loadgen_late_ms — layer: load generator (the benchmark's own).

p95 over the window's requests of (actual send - due send), in ms. A starved
generator must not be read as a fast server: where this is large, the times
to first token are the generator's doing. Open loops only: a closed loop has
no due time, and the reader returns nothing there."""

from benchmark import stats


def read(run: dict):
    late = [(r.sent - r.due) * 1000.0 for r in run["records"]
            if r.due is not None and r.sent is not None]
    return stats.percentile(late, 95) if late else None
