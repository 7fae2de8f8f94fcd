"""What the readers of the expert layers' counters share. The engine counts,
on the device and inside each decode chunk, the rows routed to each expert it
holds; the counts ride to the host with the chunk's tokens and come out as
attributes of the `engine.host_sync` span that read them: `moe_rows` (over
all held experts and expert layers), `moe_rows_busiest` (the same for the
busiest held expert) and `moe_steps` (the chunk's decode steps). A program
without expert layers, or from before they were counted, writes none: the
readers then return None."""

from __future__ import annotations

from benchmark import spans as sp


def chunks(run: dict) -> list[dict]:
    """Attributes of the window's host syncs that read a counted chunk."""
    lo, hi = run["window_wall"]
    return [s["at"] for s in sp.named(run.get("spans") or [],
                                      "engine.host_sync", lo, hi)
            if (s.get("at") or {}).get("moe_steps")]


def totals(run: dict):
    """(rows, busiest expert's rows, steps) over the window, or None."""
    got = chunks(run)
    if not got:
        return None
    return (sum(c["moe_rows"] for c in got),
            sum(c["moe_rows_busiest"] for c in got),
            sum(c["moe_steps"] for c in got))
