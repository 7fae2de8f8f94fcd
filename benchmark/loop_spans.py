"""What the readers of a looped stack's decode steps share. Since PR 53 an
`engine.dispatch_chunk` span of a model whose layers run several times a
token carries `ut_steps`, the passes, beside `tokens`, `active`,
`kv_rows_full` (the rows of ONE full leaf a slot's attention walks in each
step of the chunk) and `kv_live_full` (the rows a LIVE slot has to show
there, a step's mean): a layer keeps a K and V pair for each pass, each
walked once a step, so what the two say of one leaf holds `ut_steps` times a
layer. A program from before that, or a model that runs its layers once,
writes no `ut_steps`: the readers then return None."""

from __future__ import annotations

from benchmark import shapes_loop, spans as sp

KEYS = ("ut_steps", "kv_rows_full", "kv_live_full", "tokens", "active")


def chunks(run: dict, traced_only: bool = False) -> list[dict]:
    """Attributes of the chunks dispatched in the window (or while the
    profiler ran) that carry the passes and a leaf's rows; [] for a
    configuration without a loop over its stack."""
    if not shapes_loop.is_looped(run["config"]["llm_config"]):
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


def rows_a_step(found: list[dict], key: str) -> float:
    """`key`'s rows of ONE leaf pair summed over the live slots, a decode
    step's mean over the steps of `found`."""
    return (sum(slot_steps(c) * c[key] for c in found)
            / sum(c["tokens"] for c in found))
