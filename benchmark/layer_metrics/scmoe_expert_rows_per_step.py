"""scmoe_expert_rows_per_step — layer: model step (models/moe.py, counted in
`jit_chunk`), for a configuration whose `arch` counts `n_routed_experts` and
`zero_expert_num` (a router with identity experts; one expert layer a
published layer).

Rows (token x selected expert) that one decode step routes to the routed
experts this chip holds, per expert layer: `moe_rows / (moe_steps x expert
layers)` over the window's chunks. With routing uniform over the router's
outputs it is batch x selections a token x held / outputs (32 x 12 x 16 /
768 = 8). Every slot of the batch counts, occupied or not: the step computes
them all."""

from benchmark import engine_spans as es, moe_spans, shapes_scmoe


@es.never_raises
def read(run: dict):
    got = moe_spans.totals(run)
    llm = run["config"]["llm_config"]
    if got is None or not shapes_scmoe.is_scmoe(llm):
        return None
    rows, busiest, steps = got
    layers = shapes_scmoe.expert_layers(llm)
    batch = run["config"]["app_kwargs"]["max_batch"]
    print(f"scmoe_expert_rows_per_step: {rows} rows in {steps} steps of "
          f"{layers} expert layers (the busiest held expert of a chunk "
          f"{busiest}); uniform routing would give "
          f"{shapes_scmoe.expected_expert_rows(llm, batch) / layers:.2f}",
          flush=True)
    return rows / (steps * layers)
