"""What the readers of EVA attention's two kinds of cache leaf share. Since
PR 49 an `engine.dispatch_chunk` span of a model with "eva" layers carries,
beside `tokens` and `active`: `kv_rows_window` and `kv_rows_chunks`, the rows
of the window leaf and of the summaries leaf a slot's attention walks in
each step of the chunk, and `kv_live_window` and `kv_live_chunks`, the rows a
LIVE slot has to show there (p mod window + 1; one a chunk of every window
behind p's), a step's mean. A program from before that, or a model without
such layers, writes no `kv_*_chunks`: the readers then return None."""

from __future__ import annotations

from benchmark import shapes_eva, spans as sp

KEYS = ("kv_rows_window", "kv_rows_chunks", "kv_live_window",
        "kv_live_chunks")


def chunks(run: dict, traced_only: bool = False) -> list[dict]:
    """Attributes of the chunks dispatched in the window (or while the
    profiler ran) that carry both leaves' rows; [] for a configuration
    without EVA attention."""
    if not shapes_eva.is_eva(run["config"]["llm_config"]):
        return []
    if traced_only:
        got = sp.traced_chunks(run)
    else:
        lo, hi = run["window_wall"]
        got = sp.named(run.get("spans") or [], "engine.dispatch_chunk",
                       lo, hi)
    return [c["at"] for c in got
            if all(k in (c.get("at") or {}) for k in KEYS)]


def slot_steps(c: dict) -> float:
    """A chunk's weight in a mean over live slots' steps."""
    return c["tokens"] * c["active"]


def rows(found: list[dict], key: str) -> float:
    """`key`'s rows summed over the live slots' steps of `found`."""
    return sum(slot_steps(c) * c[key] for c in found)
