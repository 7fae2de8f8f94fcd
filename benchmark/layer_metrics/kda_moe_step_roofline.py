"""kda_moe_step_roofline — layer: kernels (the decode step of the gated
delta-rule layers, the latent attention layers and the expert layers; all of
it XLA, no Pallas kernel).

The least time the chip could take for a decode step of this model over the
time it took (`decode_step_ms`), in %. The least time is the larger of bytes
over bandwidth and operations over the bf16 peak, from
`benchmark/shapes_kda_moe.py` and `benchmark/peaks.py`: every held weight
outside the routed experts and the embedding table once; one expert's
weights for each held expert a step TOUCHED, where the program says how many
(`moe_touched` of the chunks dispatched while the profiler ran, held against
`moe_rows` and `moe_steps`: `benchmark/moe_spans.py` `touched_per_step`),
and for every held expert where it does not; the state of every slot read
and written once a KDA layer; every latent row visible to a live slot once
an MLA layer, 1152 bytes at the published sizes; the expert operations for
the rows the engine counted (`moe_rows`). Visible rows are the engine's own
count on the chunks dispatched while the profiler ran (`kv_live_full` x
`active`). The share counted on all held experts is printed beside it: the
scale of the ledger's lines up to PR 43. A program without state layers
writes no `state_rw_bytes` on its chunks: nothing is returned."""

from benchmark import (engine_spans as es, moe_spans, peaks, shapes_kda_moe,
                       spans as sp)


@es.never_raises
def read(run: dict):
    llm = run["config"]["llm_config"]
    got = sp.decode_steps(run)
    chunks = [c["at"] for c in sp.traced_chunks(run)
              if "state_rw_bytes" in (c.get("at") or {})
              and "kv_live_full" in c["at"]]
    if not shapes_kda_moe.is_kda(llm) or got is None or not chunks:
        return None
    steps, secs = got
    batch = run["config"]["app_kwargs"]["max_batch"]
    tokens = sum(c["tokens"] for c in chunks)
    rows = sum(c["kv_live_full"] * c["active"] * c["tokens"]
               for c in chunks) / tokens
    active = sum(c["active"] * c["tokens"] for c in chunks) / tokens
    counted = moe_spans.totals(run)
    expert_rows = counted[0] / counted[2] if counted else None
    peak = peaks.peaks(run["device"]["kind"])
    found = moe_spans.least_step(
        run, batch, lambda touched: shapes_kda_moe.decode_step_min_seconds(
            llm, batch, rows, peak, expert_rows, touched=touched))
    if found is None:
        return None
    least, all_held, said = found
    held = 2 * shapes_kda_moe.cache_bytes(llm, batch)["state"]
    print(f"kda_moe_step_roofline: "
          f"{moe_spans.step_said(least, all_held, said, secs / steps)}; "
          f"{active:.2f} slots active, {rows / active:.0f} latent rows "
          f"visible a slot; the engine says {chunks[0]['state_rw_bytes']} "
          f"bytes of state read and written a step, the shapes {held} with "
          f"the pending corrections", flush=True)
    return 100.0 * least["seconds"] / (secs / steps)
