"""eva_step_roofline — layer: kernels (the decode step of the EVA layers: the
walk of the window and summaries leaves, the summary a step writes, SwiGLU,
the head; all of it XLA, no Pallas kernel).

The least time the chip could take for a WHOLE decode step of this model
over the time it took (`decode_step_ms`), in %. The least time is the larger
of bytes over bandwidth and operations over the bf16 peak, from
`benchmark/shapes_eva.py` and `benchmark/peaks.py`: every held weight but the
embedding table once, and 2 x heads x head size x 2 bytes (16,384 at the
published sizes) for each row VISIBLE to a live slot in either leaf a layer.
Visible rows are the engine's own count on the chunks dispatched while the
profiler ran (`kv_live_window`, `kv_live_chunks` x `active`). Prints the
bytes by part and the limit that binds. A kernel of this model's own would
have its share printed here from the trace's `custom-call`; there is none."""

from benchmark import (engine_spans as es, eva_spans, peaks, shapes_eva,
                       spans as sp)


@es.never_raises
def read(run: dict):
    llm = run["config"]["llm_config"]
    got = sp.decode_steps(run)
    found = eva_spans.chunks(run, traced_only=True)
    if got is None or not found:
        return None
    steps, secs = got
    batch = run["config"]["app_kwargs"]["max_batch"]
    tokens = sum(c["tokens"] for c in found)
    rows_window = eva_spans.rows(found, "kv_live_window") / tokens
    rows_chunks = eva_spans.rows(found, "kv_live_chunks") / tokens
    active = sum(eva_spans.slot_steps(c) for c in found) / tokens
    least = shapes_eva.decode_step_min_seconds(
        llm, batch, rows_window, rows_chunks,
        peaks.peaks(run["device"]["kind"]))
    parts = ", ".join(f"{k} {v / 1e9:.3f} GB"
                      for k, v in least["parts"].items())
    print(f"eva_step_roofline: the least step is {least['seconds'] * 1e3:.3f}"
          f" ms ({least['bound']}: {least['bytes'] / 1e9:.3f} GB, "
          f"{least['flops'] / 1e9:.1f} GFLOP; {parts}) against "
          f"{secs / steps * 1e3:.3f} ms a step; {active:.2f} slots active, "
          f"{rows_window / active:.0f} window rows and "
          f"{rows_chunks / active:.0f} summary rows visible a slot",
          flush=True)
    return 100.0 * least["seconds"] / (secs / steps)
