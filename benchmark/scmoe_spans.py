"""What the readers of the identity experts' counters share. An expert layer
whose router has identity (zero-computation) experts counts, on the device
and inside each decode chunk, beside the rows of each held expert
(`benchmark/moe_spans.py`): `moe_picks`, the selections the chunk's rows made
(every slot of the batch x selections a token x expert layers x steps),
`moe_zero_picks`, those that fell on an identity expert, and `moe_touched`,
the held experts that got at least one row, summed over expert layers and
steps (any router may count it: `moe_spans.py` has its contract and its
reader). They come out as attributes of the `engine.host_sync` span that read
the chunk. A program without such a router, or from before they were
counted, writes none: the readers then return None."""

from __future__ import annotations

from benchmark import moe_spans


def chunks(run: dict) -> list[dict]:
    """Attributes of the window's host syncs that read a chunk of a router
    with identity experts."""
    return [c for c in moe_spans.chunks(run) if "moe_picks" in c]


def totals(run: dict, only: list[dict] | None = None):
    """{picks, zero_picks, touched, rows, steps} over the window (or over
    `only`, a subset of `chunks`: `moe_spans.traced(run, "moe_picks")` gives
    those dispatched while the profiler ran), or None."""
    got = chunks(run) if only is None else only
    if not got:
        return None
    return {"picks": sum(c["moe_picks"] for c in got),
            "zero_picks": sum(c["moe_zero_picks"] for c in got),
            "touched": sum(c["moe_touched"] for c in got),
            "rows": sum(c["moe_rows"] for c in got),
            "steps": sum(c["moe_steps"] for c in got)}

