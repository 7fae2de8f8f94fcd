"""ready_wait_ms — layer: engine scheduler (llm/engine.py `_ready`,
`_run_scheduler` step 1).

Median over the window's requests of their `engine.ready_wait` span, in ms:
from the prefill lane parking the dispatched prefill in `_ready` to the
scheduler beginning its splice. The scheduler splices only at the top of a
pass, after the blocking read of the pass before, and only into a free slot:
this is the wait for a chunk boundary and for a slot. A program with inline
admission has no such stage, and the reader returns nothing."""

from benchmark import engine_spans as es


@es.never_raises
def read(run: dict):
    spans = es.stage_spans(run, "engine.ready_wait")
    if not spans:
        return None
    waits = [(s["b"] - s["a"]) * 1000.0 for s in spans]
    active = [s["at"]["active"] for s in spans
              if "active" in (s.get("at") or {})]
    parked = [s["at"]["ready"] for s in spans
              if "ready" in (s.get("at") or {})]
    print(f"ready_wait_ms: {len(waits)} requests; slots in use at the "
          f"splice: median {es.median(active)}; already parked: median "
          f"{es.median(parked)}, most {max(parked, default=None)}", flush=True)
    return es.median(waits)
