"""engine_queue_ms — layer: proxy, router, replica, engine queue
(llm/engine.py `_pending`).

Median over the window's requests of their `engine.queue` span, in ms: from
`submit()` putting the request into `_pending` to the prefill lane taking it
out. It is the engine's own part of `admit_wait_ms`, which runs from the
proxy's root span to the start of the prefill's dispatch and so contains
it."""

from benchmark import engine_spans as es


@es.never_raises
def read(run: dict):
    waits = es.stage_ms(run, "engine.queue")
    if not waits:
        return None
    depth = [s["at"]["pending"] for s in es.stage_spans(run, "engine.queue")
             if "pending" in (s.get("at") or {})]
    print(f"engine_queue_ms: {len(waits)} requests; already queued at "
          f"submit: median {es.median(depth)}, most {max(depth, default=None)}",
          flush=True)
    return es.median(waits)
