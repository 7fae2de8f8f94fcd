"""admit_wait_ms — layer: proxy, router, replica, engine queue
(serve/_private/proxy.py, router.py, replica.py, llm/engine.py `_pending`).

Median over the window's requests of (start of the request's `engine.prefill`
span - start of its `http POST` root span at the proxy), in ms: the time a
request spends in the runtime's own layers before the engine takes it up.
Both spans are the program's (RT_TRACING=1, every request sampled)."""

from benchmark import spans as sp, stats


def read(run: dict):
    lo, hi = run["window_wall"]
    roots = sp.request_roots(run["spans"])
    waits = [(s["a"] - roots[s["t"]]["a"]) * 1000.0
             for s in run["spans"]
             if s["n"] == "engine.prefill" and s["t"] in roots
             and lo <= roots[s["t"]]["a"] < hi]
    return stats.percentile(waits, 50) if waits else None
