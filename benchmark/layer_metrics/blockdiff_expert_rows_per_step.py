"""blockdiff_expert_rows_per_step — layer: model step (models/moe.py, counted
in `jit_chunk`), for a configuration that generates by diffusion over blocks.

Rows (position x selected expert) that one FORWARD routes to a held expert,
per expert layer: `moe_rows / (held x expert layers x moe_steps)` over the
window's chunks. With uniform routing it is batch x L x experts per token /
published experts (32 x 4 x 8 / 128 = 8): what a block step buys the expert
layer over a step of one position a slot, which would give each expert 2.
Every slot of the batch counts, occupied or not: the forward computes them
all."""

from benchmark import engine_spans as es, moe_spans, shapes_blockdiff


@es.never_raises
def read(run: dict):
    got = moe_spans.totals(run)
    llm = run["config"]["llm_config"]
    if got is None or not shapes_blockdiff.is_blockdiff(llm):
        return None
    rows, busiest, steps = got
    layers = shapes_blockdiff.expert_layers(llm)
    held = shapes_blockdiff.experts_held(llm)
    batch = run["config"]["app_kwargs"]["max_batch"]
    uniform = shapes_blockdiff.expected_expert_rows(llm, batch) / (
        layers * held)
    print(f"blockdiff_expert_rows_per_step: {rows} rows in {steps} forwards "
          f"of {layers} expert layers of {held} held experts (the busiest "
          f"held expert of a chunk {busiest}); uniform routing would give "
          f"{uniform:.2f}", flush=True)
    return rows / (held * layers * steps)
