"""eva_walk_over_visible — layer: model step (ops/decode_attention.py
`two_leaf_decode_attention` through `jit_chunk`: the walk of the window leaf
and of the summaries leaf of an "eva" layer).

Rows of cache a live slot's decode steps WALKED over the rows VISIBLE to it,
both leaves together, over the window's chunks (every layer keeps the same
two leaves, so the layers cancel): (`kv_rows_window` + `kv_rows_chunks`)
over (`kv_live_window` + `kv_live_chunks`), each chunk weighted by its steps
and its live slots. 1.0 is a ragged walk that stops at each slot's own rows
in both leaves; the XLA walk stops each leaf at the quarter that holds its
longest live stop. The counterpart of `kv_walk_over_visible`."""

from benchmark import engine_spans as es, eva_spans


@es.never_raises
def read(run: dict):
    found = eva_spans.chunks(run)
    if not found:
        return None
    walked = {k: eva_spans.rows(found, "kv_rows_" + k)
              for k in ("window", "chunks")}
    visible = {k: eva_spans.rows(found, "kv_live_" + k)
               for k in ("window", "chunks")}
    if not sum(visible.values()):
        return None
    print(f"eva_walk_over_visible: {len(found)} chunks; " + ", ".join(
        f"{k} leaf {walked[k] / visible[k]:.3f}" if visible[k]
        else f"{k} leaf: none visible" for k in walked), flush=True)
    return sum(walked.values()) / sum(visible.values())
