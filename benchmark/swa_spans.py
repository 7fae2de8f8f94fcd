"""What the readers of the two kinds of cache leaf share. Since PR 32 an
`engine.dispatch_chunk` span carries, beside `tokens`, `active` and
`kv_bound`: `kv_rows_full` and `kv_rows_window`, the rows a slot's attention
walks in a full and in a window layer in each step of the chunk, and
`kv_live_full` and `kv_live_window`, the rows a LIVE slot has to show there,
a step's mean. A program from before that, or a model without window layers,
writes none or only the full kind: the readers then return None."""

from __future__ import annotations

from benchmark import spans as sp

KEYS = ("kv_rows_full", "kv_rows_window", "kv_live_full", "kv_live_window")


def is_swa(llm: dict) -> bool:
    """A configuration whose `arch` names window layers."""
    arch = llm.get("arch") or {}
    return "layer_types" in arch and "sliding_window" in arch


def chunks(run: dict, traced_only: bool = False) -> list[dict]:
    """Attributes of the chunks dispatched in the window (or while the
    profiler ran) that carry both kinds."""
    if traced_only:
        got = sp.traced_chunks(run)
    else:
        lo, hi = run["window_wall"]
        got = sp.named(run.get("spans") or [], "engine.dispatch_chunk",
                       lo, hi)
    return [c["at"] for c in got
            if all(k in (c.get("at") or {}) for k in KEYS)]
