"""kda_expert_rows_per_step — layer: model step (models/moe.py, counted in
`jit_chunk`), for a configuration whose `arch` counts its experts as
`num_experts` and its leading dense layers as `first_k_dense_replace`
beside a `linear_attn_config`.

Rows (token x selected expert) that one decode step routes to the experts
this chip holds, per expert layer: `moe_rows / (moe_steps x expert layers)`
over the window's chunks. With uniform routing it is batch x experts per
token x held / published (64 x 8 x 16 / 256 = 32). Every slot of the batch
counts, occupied or not: the step computes them all."""

from benchmark import engine_spans as es, moe_spans, shapes_kda_moe


@es.never_raises
def read(run: dict):
    got = moe_spans.totals(run)
    llm = run["config"]["llm_config"]
    if got is None or not shapes_kda_moe.is_kda(llm):
        return None
    rows, busiest, steps = got
    layers = shapes_kda_moe.expert_layers(llm)
    batch = run["config"]["app_kwargs"]["max_batch"]
    print(f"kda_expert_rows_per_step: {rows} rows in {steps} steps of "
          f"{layers} expert layers (the busiest held expert of a chunk "
          f"{busiest}); uniform routing would give "
          f"{shapes_kda_moe.expected_expert_rows(llm, batch) / layers:.2f}",
          flush=True)
    return rows / (steps * layers)
