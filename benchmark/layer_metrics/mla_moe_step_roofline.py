"""mla_moe_step_roofline — layer: kernels (the decode step of the latent
attention and the expert layers; all of it XLA, no Pallas kernel).

The least time the chip could take for a decode step of this model over the
time it took (`decode_step_ms`), in %. The least time is the larger of bytes
over bandwidth and operations over the bf16 peak, from
`benchmark/shapes_mla_moe.py` and `benchmark/peaks.py`: every held weight
outside the routed experts and the embedding table once; one expert's
weights for each held expert a step TOUCHED, where the program says how many
(`moe_touched` of the chunks dispatched while the profiler ran, held against
`moe_rows` and `moe_steps`: `benchmark/moe_spans.py` `touched_per_step`),
and for every held expert where it does not; the valid rows of the latent
cache once; the expert operations for the rows the engine counted
(`moe_rows`). Valid rows are estimated as `decode_step_roofline` estimates
them: active slots x the mean context, prompt plus half the answer, of the
window's requests. The share counted on all held experts is printed beside
it: the scale of the ledger's lines up to PR 43."""

from benchmark import (engine_spans as es, moe_spans, peaks, shapes_mla_moe,
                       spans as sp)


@es.never_raises
def read(run: dict):
    llm = run["config"]["llm_config"]
    got = sp.decode_steps(run)
    done = [r for r in run["records"] if r.ok]
    chunks = sp.traced_chunks(run)
    if not llm.get("arch") or got is None or not done or not chunks:
        return None
    steps, secs = got
    batch = run["config"]["app_kwargs"]["max_batch"]
    context = sum(r.plen + r.n_tokens / 2 for r in done) / len(done)
    active = (sum(c["at"]["active"] * c["at"]["tokens"] for c in chunks)
              / sum(c["at"]["tokens"] for c in chunks))
    counted = moe_spans.totals(run)
    expert_rows = counted[0] / counted[2] if counted else None
    peak = peaks.peaks(run["device"]["kind"])
    found = moe_spans.least_step(
        run, batch, lambda touched: shapes_mla_moe.decode_step_min_seconds(
            llm, batch, active * context, peak, expert_rows, touched=touched))
    if found is None:
        return None
    least, all_held, said = found
    print(f"mla_moe_step_roofline: "
          f"{moe_spans.step_said(least, all_held, said, secs / steps)}; "
          f"{active:.2f} slots active at a mean context of {context:.0f}",
          flush=True)
    return 100.0 * least["seconds"] / (secs / steps)
