"""expert_rows_per_step — layer: model step (models/moe.py, counted in
`jit_chunk`).

Rows (token x selected expert) that one decode step routes to the experts
this chip holds, per expert layer: `moe_rows / (moe_steps x expert layers)`
over the window's chunks. With uniform routing it is batch x experts per
token x held / published (32 x 8 x 12 / 384 = 8); the held experts' matrix
products are bound by their weights while it stays far below ~240 rows an
expert. Every slot of the batch counts, occupied or not: the step computes
them all."""

from benchmark import engine_spans as es, moe_spans, shapes_mla_moe


@es.never_raises
def read(run: dict):
    got = moe_spans.totals(run)
    llm = run["config"]["llm_config"]
    if got is None or not llm.get("arch"):
        return None
    rows, _busiest, steps = got
    layers = shapes_mla_moe.expert_layers(llm)
    batch = run["config"]["app_kwargs"]["max_batch"]
    print(f"expert_rows_per_step: {rows} rows in {steps} steps of {layers} "
          f"expert layers; uniform routing would give "
          f"{shapes_mla_moe.expected_expert_rows(llm, batch) / layers:.2f}",
          flush=True)
    return rows / (steps * layers)
