"""kv_walk_over_visible — layer: model step (ops/decode_attention.py through
`jit_chunk`: the walk of the two kinds of cache leaf).

Rows of cache a live slot's decode steps WALKED over the rows VISIBLE to it,
over the window's chunks and all layers: (`kv_rows_full` x full layers +
`kv_rows_window` x window layers) over (`kv_live_full` x full layers +
`kv_live_window` x window layers), each chunk weighted by its steps and its
live slots. 1.0 is a ragged walk that stops at each slot's own rows. A full
leaf is walked to the quarter of `max_seq` that holds the longest live
slot, a ring to the quarter of the window, and exactly once it has wrapped.
Without rings (every layer keeping `max_seq` rows and walking them like a
full layer) the same traffic reads about 2.5."""

from benchmark import engine_spans as es, shapes_swa_moe, swa_spans


@es.never_raises
def read(run: dict):
    llm = run["config"]["llm_config"]
    chunks = swa_spans.chunks(run)
    if not swa_spans.is_swa(llm) or not chunks:
        return None
    n_full = shapes_swa_moe.full_layers(llm)
    n_window = shapes_swa_moe.window_layers(llm)
    weight = lambda c: c["tokens"] * c["active"]  # noqa: E731
    walked = sum(weight(c) * (n_full * c["kv_rows_full"]
                              + n_window * c["kv_rows_window"])
                 for c in chunks)
    visible = sum(weight(c) * (n_full * c["kv_live_full"]
                               + n_window * c["kv_live_window"])
                  for c in chunks)
    by_kind = {kind: sum(weight(c) * c["kv_rows_" + kind] for c in chunks)
               / sum(weight(c) * c["kv_live_" + kind] for c in chunks)
               for kind in ("full", "window")}
    no_rings = (sum(weight(c) * c["kv_rows_full"] for c in chunks)
                * (n_full + n_window) / visible)
    print(f"kv_walk_over_visible: {len(chunks)} chunks; full layers "
          f"{by_kind['full']:.3f}, window layers {by_kind['window']:.3f}; "
          f"with every layer walked like a full one over the same visible "
          f"rows: {no_rings:.3f}", flush=True)
    return walked / visible if visible else None
