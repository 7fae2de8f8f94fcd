"""blockdiff_step_roofline — layer: kernels (a forward of a block-diffusion
model: the ragged decode kernel handed L x 8 query rows a key/value head, the
expert layers' dense arm, the head and the sampler over batch x L rows).

The least time the chip could take for one FORWARD (a scan step of
`jit_chunk`: L positions of every slot) over the time it took
(`decode_step_ms`), in %. The least time is the larger of bytes over
bandwidth and operations over the bf16 peak, from
`benchmark/shapes_blockdiff.py` and `benchmark/peaks.py`: every held weight
outside the routed experts and the embedding table once; the embedding's
rows of the forward's inputs; one expert's weights for each expert a forward
TOUCHED, where the program says how many (`moe_touched` of the chunks
dispatched while the profiler ran, held against `moe_rows` and `moe_steps`:
`benchmark/moe_spans.py` `touched_per_step`, an expert getting at most L rows
a slot), and every held expert where it does not; K and V rows [0, committed
+ L) of each live slot once a forward, not once a query (`kv_live_full` x
`active` of the chunks dispatched while the profiler ran); the operations of
batch x L rows. The share counted on all held experts is printed beside it.
It is the step's share and each kernel's bound; the block step's attention
is the `mha` family's ragged kernel (no new Pallas call of its own), whose
share of a forward's time is printed where the trace names it."""

from benchmark import (engine_spans as es, moe_spans, peaks, shapes_blockdiff,
                       spans as sp)


def kernel_share(run: dict) -> str:
    """The Mosaic calls' share of the traced `jit_chunk` time (the ragged
    kernel is a forward's only one), where the reduced trace's operations
    of most self time list them."""
    shares = []
    for dev in (run.get("profile") or {}).get("devices") or []:
        chunk = dev["programs"].get("jit_chunk", 0.0)
        secs = sum(secs for label, secs in dev.get("ops") or []
                   if "custom-call" in label)
        if chunk and secs:
            shares.append(secs / chunk)
    return (f"; the ragged kernel's calls take "
            f"{100 * sum(shares) / len(shares):.1f}% of a forward"
            if shares else "")


@es.never_raises
def read(run: dict):
    llm = run["config"]["llm_config"]
    got = sp.decode_steps(run)
    chunks = [c["at"] for c in sp.traced_chunks(run)
              if "kv_live_full" in (c.get("at") or {})
              and "block_length" in c["at"]]
    if not shapes_blockdiff.is_blockdiff(llm) or got is None or not chunks:
        return None
    steps, secs = got
    batch = run["config"]["app_kwargs"]["max_batch"]
    size = shapes_blockdiff.block_length(llm)
    forwards = sum(c["tokens"] for c in chunks)  # a chunk's steps
    rows = sum(c["kv_live_full"] * c["active"] * c["tokens"]
               for c in chunks) / forwards
    active = sum(c["active"] * c["tokens"] for c in chunks) / forwards
    counted = moe_spans.totals(run)
    expert_rows = counted[0] / counted[2] if counted else None
    peak = peaks.peaks(run["device"]["kind"])
    found = moe_spans.least_step(
        run, batch * size, lambda touched: shapes_blockdiff.forward_min_seconds(
            llm, batch, rows, peak, expert_rows, touched=touched))
    if found is None:
        return None
    least, all_held, said = found
    print(f"blockdiff_step_roofline: "
          f"{moe_spans.step_said(least, all_held, said, secs / steps)}; "
          f"{active:.2f} slots active of {batch}, {size} positions each, "
          f"{rows / active:.0f} rows visible a slot{kernel_share(run)}",
          flush=True)
    return 100.0 * least["seconds"] / (secs / steps)
