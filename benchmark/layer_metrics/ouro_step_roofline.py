"""ouro_step_roofline — layer: kernels (the decode step of a looped stack:
the `mha` family's ragged kernel over each pass's own leaves, 32 calls a
step at 8 layers x 4 passes, the projections, SwiGLU, the norms, the gate,
the head).

The least time the chip could take for a WHOLE decode step of this model
over the time it took (`decode_step_ms`), in %. The least time is the larger
of bytes over bandwidth and operations over the bf16 peak, from
`benchmark/shapes_loop.py` and `benchmark/peaks.py`: every held layer weight
`ut_steps` times (pass t + 1 of a token needs pass t whole, and all the
other layers' weights, 411 MB here, pass between two readings of one matrix:
no on-chip memory keeps it), the head, the final norm and the gate once, the
embedding table not (16 rows), and 2 x key/value heads x head size x 2 bytes
(8,192 at the published sizes) for each row VISIBLE to a live slot a layer a
pass. Visible rows are the engine's own count on the chunks dispatched while
the profiler ran (`kv_live_full` x `active`, one leaf's; x layers x
`ut_steps`). Prints the bytes by part, the limit that binds, and the rows
the kernel read over the rows visible. The step's attention is a kernel the
benchmark already reads elsewhere; this PR brings none of its own."""

from benchmark import (engine_spans as es, loop_spans, peaks, shapes_loop,
                       spans as sp)


@es.never_raises
def read(run: dict):
    llm = run["config"]["llm_config"]
    got = sp.decode_steps(run)
    found = loop_spans.chunks(run, traced_only=True)
    if got is None or not found:
        return None
    steps, secs = got
    batch = run["config"]["app_kwargs"]["max_batch"]
    visible = loop_spans.rows_a_step(found, "kv_live_full")
    walked = loop_spans.rows_a_step(found, "kv_rows_full")
    active = (sum(loop_spans.slot_steps(c) for c in found)
              / sum(c["tokens"] for c in found))
    least = shapes_loop.decode_step_min_seconds(
        llm, batch, visible, peaks.peaks(run["device"]["kind"]))
    parts = ", ".join(f"{k} {v / 1e9:.3f} GB"
                      for k, v in least["parts"].items())
    print(f"ouro_step_roofline: the least step is "
          f"{least['seconds'] * 1e3:.3f} ms ({least['bound']}: "
          f"{least['bytes'] / 1e9:.3f} GB, {least['flops'] / 1e9:.1f} GFLOP; "
          f"{parts}) against {secs / steps * 1e3:.3f} ms a step; "
          f"{shapes_loop.passes(llm)} passes, {active:.2f} slots active, "
          f"{visible / active:.0f} rows visible a slot a leaf, rows read "
          f"over rows visible {walked / visible:.3f}", flush=True)
    return 100.0 * least["seconds"] / (secs / steps)
