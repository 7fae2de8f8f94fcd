"""eva_summary_row_share — layer: model step (models/eva.py: what the
traffic gives the mechanism).

Of the cache rows visible to live slots' decode steps, the share that are
SUMMARIES, in %: `kv_live_chunks` over `kv_live_window` + `kv_live_chunks`,
each chunk of the window weighted by its steps and its live slots. 0 where
every slot is still in its first window, and the layer is plain attention;
a context of 16384 positions shows 896 summaries beside at most 2048 rows. A
later change of the mix that takes the work away from the summaries cannot
pass unseen."""

from benchmark import engine_spans as es, eva_spans


@es.never_raises
def read(run: dict):
    found = eva_spans.chunks(run)
    if not found:
        return None
    window = eva_spans.rows(found, "kv_live_window")
    summaries = eva_spans.rows(found, "kv_live_chunks")
    steps = sum(eva_spans.slot_steps(c) for c in found)
    if not window + summaries:
        return None
    print(f"eva_summary_row_share: a live slot's step sees "
          f"{window / steps:.0f} window rows and {summaries / steps:.0f} "
          f"summary rows", flush=True)
    return 100.0 * summaries / (window + summaries)
