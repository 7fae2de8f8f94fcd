"""swa_moe_step_roofline — layer: kernels (the decode step of the window and
full attention layers and the expert layers; all of it XLA, no Pallas
kernel).

The least time the chip could take for a decode step of this model over the
time it took (`decode_step_ms`), in %. The least time is the larger of bytes
over bandwidth and operations over the bf16 peak, from
`benchmark/shapes_swa_moe.py` and `benchmark/peaks.py`: every held weight
outside the routed experts and the embedding table once; one expert's
weights for each held expert a step TOUCHED, where the program says how many
(`moe_touched` of the chunks dispatched while the profiler ran, held against
`moe_rows` and `moe_steps`: `benchmark/moe_spans.py` `touched_per_step`),
and for every held expert where it does not; every cache row visible to a
live slot once, its context in a full layer and at most the window in a
window layer, 2048 bytes a row a layer at the published sizes; the expert
operations for the rows the engine counted (`moe_rows`). Visible rows are
the engine's own count on the chunks dispatched while the profiler ran
(`kv_live_full`, `kv_live_window` x `active`). The share counted on all held
experts is printed beside it: the scale of the ledger's lines up to PR 43."""

from benchmark import (engine_spans as es, moe_spans, peaks, shapes_swa_moe,
                       spans as sp, swa_spans)


@es.never_raises
def read(run: dict):
    llm = run["config"]["llm_config"]
    got = sp.decode_steps(run)
    chunks = swa_spans.chunks(run, traced_only=True)
    if not swa_spans.is_swa(llm) or got is None or not chunks:
        return None
    steps, secs = got
    batch = run["config"]["app_kwargs"]["max_batch"]
    tokens = sum(c["tokens"] for c in chunks)
    mean = lambda key: (sum(c[key] * c["active"] * c["tokens"]  # noqa: E731
                            for c in chunks) / tokens)
    rows_full, rows_window = mean("kv_live_full"), mean("kv_live_window")
    active = sum(c["active"] * c["tokens"] for c in chunks) / tokens
    counted = moe_spans.totals(run)
    expert_rows = counted[0] / counted[2] if counted else None
    peak = peaks.peaks(run["device"]["kind"])
    found = moe_spans.least_step(
        run, batch, lambda touched: shapes_swa_moe.decode_step_min_seconds(
            llm, batch, rows_full, rows_window, peak, expert_rows,
            touched=touched))
    if found is None:
        return None
    least, all_held, said = found
    print(f"swa_moe_step_roofline: "
          f"{moe_spans.step_said(least, all_held, said, secs / steps)}; "
          f"{active:.2f} slots active, {rows_full / active:.0f} rows visible "
          f"a slot in a full layer and {rows_window / active:.0f} in a "
          f"window layer", flush=True)
    return 100.0 * least["seconds"] / (secs / steps)
