"""mla_moe_step_roofline — layer: kernels (the decode step of the latent
attention and the expert layers; all of it XLA, no Pallas kernel).

The least time the chip could take for a decode step of this model over the
time it took (`decode_step_ms`), in %. The least time is the larger of bytes
over bandwidth and operations over the bf16 peak, from
`benchmark/shapes_mla_moe.py` and `benchmark/peaks.py`: every held weight
but the embedding table once, the valid rows of the latent cache once, the
expert operations for the rows the engine counted (`moe_rows`). Valid rows
are estimated as `decode_step_roofline` estimates them: active slots x the
mean context, prompt plus half the answer, of the window's requests."""

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
    least = shapes_mla_moe.decode_step_min_seconds(
        llm, batch, active * context, peaks.peaks(run["device"]["kind"]),
        expert_rows)
    parts = ", ".join(f"{k} {v / 1e9:.3f}" for k, v in sorted(
        least["parts"].items(), key=lambda kv: -kv[1]))
    print(f"mla_moe_step_roofline: least step {least['seconds'] * 1e3:.3f} "
          f"ms ({least['bytes'] / 1e9:.3f} GB, {least['flops'] / 1e12:.3f} "
          f"TFLOP, bound by {least['bound']}); GB by part: {parts}; "
          f"{active:.2f} slots active at a mean context of {context:.0f}",
          flush=True)
    return 100.0 * least["seconds"] / (secs / steps)
