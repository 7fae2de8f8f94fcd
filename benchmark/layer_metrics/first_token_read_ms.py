"""first_token_read_ms — layer: engine scheduler (llm/engine.py `_splice`,
`_deliver`).

Median over the window's requests of their `engine.first_token` span, in ms:
from the scheduler beginning the splice to the first token being put on the
request's stream. The first token is read back with the oldest chunk in
flight, behind every chunk dispatched before the splice, so this follows the
depth of the pipeline (`chunks_in_flight`, printed).

Printed beside it, the split of the time to first token: the medians of
`admit_wait_ms` (proxy's root span to the start of the prefill's dispatch;
it contains `engine.queue`), the `engine.prefill` dispatch, `engine.ready_wait`
and `engine.first_token`, their sum, and `ttft_p50_ms` (due to first SSE
token, client side). Medians of skewed stages do not add up to the median
of their sum, so the same split is printed as means, which do, and with it
the median and the mean over the requests of (first token put on the stream
- start of the root span): the four stages of one request laid end to end.
What is left of the client's time beyond that is the way back (stream,
replica, proxy, SSE) and the way in before the root span."""

from benchmark import engine_spans as es, manifest


@es.never_raises
def read(run: dict):
    spans = es.stage_spans(run, "engine.first_token")
    if not spans:
        return None
    reads = [(s["b"] - s["a"]) * 1000.0 for s in spans]
    depth = [s["at"]["chunks_in_flight"] for s in spans
             if "chunks_in_flight" in (s.get("at") or {})]
    print(f"first_token_read_ms: {len(reads)} requests; chunks in flight at "
          f"the splice: median {es.median(depth)}, most "
          f"{max(depth, default=None)}", flush=True)
    value = es.median(reads)
    parts = {"admit_wait": manifest.layer_reader("admit_wait_ms")(run),
             "prefill_dispatch": es.median(es.stage_ms(run, "engine.prefill")),
             "ready_wait": es.median(es.stage_ms(run, "engine.ready_wait")),
             "first_token": value}
    ttft = manifest.layer_reader("ttft_p50_ms")(run)
    known = sum(v for v in parts.values() if v is not None)
    line = ", ".join(f"{k} {v:.1f}" if v is not None else f"{k} none"
                     for k, v in parts.items())
    print(f"first_token_read_ms: time to first token, medians in ms: {line}; "
          f"sum {known:.1f}" + (f" of ttft_p50_ms {ttft:.1f}, remainder "
                                f"{ttft - known:.1f}" if ttft is not None
                                else " (a closed loop has no due time)"),
          flush=True)
    roots = es.window_roots(run)
    whole = [(s["b"] - roots[s["t"]]["a"]) * 1000.0 for s in spans]
    means = {"admit_wait": [(s["a"] - roots[s["t"]]["a"]) * 1000.0 for s in
                            es.stage_spans(run, "engine.prefill")],
             "prefill_dispatch": es.stage_ms(run, "engine.prefill"),
             "ready_wait": es.stage_ms(run, "engine.ready_wait"),
             "first_token": reads}
    done = [(r.t_first - r.due) * 1000.0 for r in run.get("records") or []
            if r.due is not None and r.ok]
    print("first_token_read_ms: the same as means: " + ", ".join(
        f"{k} {sum(v) / len(v):.1f}" for k, v in means.items() if v)
        + f"; root span to first token put, per request: median "
        f"{es.median(whole):.1f}, mean {sum(whole) / len(whole):.1f}"
        + (f"; the client's due to first token, completed requests: median "
           f"{es.median(done):.1f}, mean {sum(done) / len(done):.1f}"
           if done else ""), flush=True)
    return value
