"""What the readers of the engine's own spans share (the engine's table in
the README's "Tracing & timeline"): a request's stages inside the engine
(`engine.queue`, `engine.prefill`, `engine.ready_wait`, `engine.first_token`)
and the scheduler's passes (`engine.iteration`).

`benchmark/run.py` calls a reader without a `try`, and an exception there
ends a traced run with exit code 1. So every reader of this PR's metrics is
wrapped in `never_raises`: where the spans are missing (a program that does
not write them, an empty run) it returns None by itself, and where they are
odd in a way nobody foresaw it says so and returns None."""

from __future__ import annotations

import functools
import traceback

from benchmark import spans as sp, stats


def never_raises(read):
    """A reader that returns None, with the traceback printed, where it
    would have raised."""
    @functools.wraps(read)
    def safe(run):
        try:
            return read(run)
        except Exception:  # noqa: BLE001 - the run goes on without the metric
            print(f"{read.__module__}: no value, the reader failed:\n"
                  f"{traceback.format_exc()}", flush=True)
            return None
    return safe


def median(values):
    return stats.percentile(values, 50) if values else None


def window_roots(run: dict) -> dict[str, dict]:
    """trace id -> root span of the completion requests that reached the
    proxy inside the window."""
    lo, hi = run["window_wall"]
    return {t: r for t, r in sp.request_roots(run.get("spans") or []).items()
            if lo <= r["a"] < hi}


def stage_spans(run: dict, name: str) -> list[dict]:
    """The `name` spans of the window's requests."""
    roots = window_roots(run)
    return [s for s in run.get("spans") or []
            if s.get("n") == name and s.get("t") in roots]


def stage_ms(run: dict, name: str) -> list[float]:
    return [(s["b"] - s["a"]) * 1000.0 for s in stage_spans(run, name)]


def iterations(run: dict) -> list[dict]:
    """The `engine.iteration` spans that start inside the window and carry
    every phase."""
    lo, hi = run["window_wall"]
    need = ("admit_ms", "dispatch_ms", "sync_ms", "deliver_ms", "idle_ms")
    return [s for s in sp.named(run.get("spans") or [], "engine.iteration",
                                lo, hi)
            if all(k in (s.get("at") or {}) for k in need)]
