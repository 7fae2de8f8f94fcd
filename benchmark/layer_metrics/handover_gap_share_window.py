"""handover_gap_share_window — layer: engine scheduler (`_drain`, `_deliver`,
`_admit`, `_splice` up to the next chunk's dispatch), over the WHOLE window
and from the engine's own spans (`benchmark/device_account.py`).

Share of the window's wall time, in %, that the HOST took between learning
that the last chunk in flight was done and beginning the dispatch of the
next: over the chunks enqueued into an empty pipeline (`in_flight` 0 on
`engine.dispatch_chunk`), the sum of (the start of their dispatch - the
`block_ready` of the chunk before), over the window's wall seconds a
replica. A host figure, not the device's idle time: a prefill the lane had
already enqueued runs in such a gap, and the device learns of the next chunk
later than its dispatch began. What the device stood idle at hand-overs is
`admit_dev_share_window`'s printed rest, and the trace's
`1 - busy_s / window_s`, printed here beside the same sum over the traced
second for the reader's eye only: the two are different quantities.

The gap by what the host was doing: what is left of the read (the
hand-overs' first tokens), the delivery of the tokens just read, a wait for
work if nobody is seated (`idle_ms` of the passes that began in the gap),
and the next pass up to the chunk's dispatch (hand-overs). Printed by those
phases, from the `engine.host_sync` and `engine.iteration` spans the gap
falls into."""

from benchmark import device_account as da, engine_spans as es


def gaps(run: dict, pid=None):
    """[(chunk before, chunk, seconds, of them waited for work)] of the
    chunks enqueued into an empty pipeline."""
    return [(before, c, *da.dry_gap(run, before, c))
            for p, cs in da.chunks(run).items() if pid is None or p == pid
            for before, c in zip(cs, cs[1:])
            if c.seq == before.seq + 1 and c.in_flight == 0
            and before.ready is not None]


def phases(run: dict, found) -> dict:
    """The gaps' seconds by what the host was doing: the rest of the read,
    the delivery (to the end of the pass the read belongs to), the wait for
    work where nobody was seated, and the rest of the next pass or passes
    up to the dispatch."""
    ends = {}
    for s in run.get("spans") or []:
        if s.get("n") == "engine.iteration":
            ends.setdefault(s.get("pid"), []).append((s["a"], s["b"]))
    out = {"sync (first tokens)": 0.0, "deliver": 0.0, "wait for work": 0.0,
           "admit, dispatch": 0.0}
    for before, c, gap, wait in found:
        stop = before.ready + gap
        sync_end = min(stop, before.sync_end or before.ready)
        pass_end = next((b for a, b in ends.get(c.pid, ())
                         if a <= sync_end <= b), sync_end)
        pass_end = min(stop, max(sync_end, pass_end))
        out["sync (first tokens)"] += max(0.0, sync_end - before.ready)
        out["deliver"] += pass_end - sync_end
        out["wait for work"] += min(wait, stop - pass_end)
        out["admit, dispatch"] += stop - pass_end - min(wait, stop - pass_end)
    return out


@es.never_raises
def read(run: dict):
    lo, hi = run["window_wall"]
    got = da.seen(run, lo, hi)
    if not got:
        return None
    found = [g for g in gaps(run) if lo <= g[0].ready < hi]
    wall = (hi - lo) * da.replicas(run)
    dry = sum(gap for _b, _c, gap, _w in found)
    print(f"handover_gap_share_window: {len(found)} of {len(got)} chunks were "
          f"enqueued into an empty pipeline; the gaps before them "
          f"{dry:.3f}s of {wall:.1f}s: "
          + ", ".join(f"{k} {v:.3f}s" for k, v in phases(run, found).items())
          + f"; with an admission program ahead "
          f"{sum(1 for _b, c, _g, _w in found if c.prefills + c.places)} of them, "
          f"median gap "
          f"{1e3 * es.median([g for _b, _c, g, _w in found] or [0]):.2f} ms",
          flush=True)
    second = da.traced_second(run)
    if second and second[1] > second[0]:
        lo2, hi2, pid = second
        clipped = [max(0.0, min(c.dispatched, hi2) - max(b.ready, lo2))
                   for b, c, _g, _w in gaps(run, pid)]
        busy, extent = da.traced_device(run)
        print(f"handover_gap_share_window: in the traced second "
              f"{100 * sum(clipped) / (hi2 - lo2):.2f}% over "
              f"{sum(1 for g in clipped if g > 0)} dry dispatches (the host's "
              f"gaps); the device's own idle share in the trace "
              f"{100 * (1 - busy / extent):.2f}%",
              flush=True)
    return 100.0 * dry / wall
