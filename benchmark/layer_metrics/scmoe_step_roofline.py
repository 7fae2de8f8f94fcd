"""scmoe_step_roofline — layer: kernels (the decode step of a layer with two
latent attentions, two dense feed-forwards and an expert layer on a
shortcut: the latent walk is PR 41's Pallas kernel, the rest XLA).

The least time the chip could take for a decode step of this model over the
time it took (`decode_step_ms`), in %. The least time is the larger of bytes
over bandwidth and operations over the bf16 peak, from
`benchmark/shapes_scmoe.py` and `benchmark/peaks.py`: every held weight
outside the experts and the embedding table once; one expert's weights for
each held expert a step TOUCHED (`moe_touched` of the chunks dispatched
while the profiler ran, held against `moe_rows` and `moe_steps`:
`benchmark/moe_spans.py` `touched_per_step`, which the three older expert
rooflines read too; an expert that got no row need not be read, and with 8
rows a step over 16 held experts most get none); every latent row visible to
a live slot once a LEAF, two leaves a layer, 1152 bytes at the published
sizes (`kv_live_full` x `active` on the traced chunks); the expert
operations for the rows the engine counted (`moe_rows`). The share counted
on all held experts is printed beside it. A program that counts no
`moe_picks` gives nothing."""

from benchmark import (engine_spans as es, moe_spans, peaks, scmoe_spans,
                       shapes_scmoe, spans as sp)


@es.never_raises
def read(run: dict):
    llm = run["config"]["llm_config"]
    got = sp.decode_steps(run)
    chunks = [c["at"] for c in sp.traced_chunks(run)
              if "kv_live_full" in (c.get("at") or {})]
    counted = scmoe_spans.totals(run, moe_spans.traced(run, "moe_picks"))
    if (not shapes_scmoe.is_scmoe(llm) or got is None or not chunks
            or counted is None):
        return None
    steps, secs = got
    batch = run["config"]["app_kwargs"]["max_batch"]
    tokens = sum(c["tokens"] for c in chunks)
    rows = sum(c["kv_live_full"] * c["active"] * c["tokens"]
               for c in chunks) / tokens
    active = sum(c["active"] * c["tokens"] for c in chunks) / tokens
    peak = peaks.peaks(run["device"]["kind"])
    found = moe_spans.least_step(
        run, batch, lambda touched: shapes_scmoe.decode_step_min_seconds(
            llm, batch, rows, peak,
            expert_rows=counted["rows"] / counted["steps"], touched=touched))
    if found is None:
        return None
    least, all_held, said = found
    step = secs / steps
    print(f"scmoe_step_roofline: "
          f"{moe_spans.step_said(least, all_held, said, step)}; "
          f"{active:.2f} slots active, {rows / max(active, 1e-9):.0f} latent "
          f"rows visible a slot a leaf, {shapes_scmoe.latent_leaves(llm)} "
          f"leaves", flush=True)
    return 100.0 * least["seconds"] / step
