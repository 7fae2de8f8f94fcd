"""expert_load_imbalance — layer: model step (models/moe.py, counted in
`jit_chunk`).

Rows of the busiest held expert over the mean of the held experts, chunk by
chunk (a chunk's counts are summed over its steps and the expert layers),
weighted by the chunks' rows: 1.0 is an even load. In a deployment the
busiest expert sets the expert layer's time; here, while every held expert's
weights are read whatever the routing, it costs nothing, which is what the
steadiness of `decode_step_ms` shows."""

from benchmark import engine_spans as es, moe_spans, shapes_mla_moe


@es.never_raises
def read(run: dict):
    got = moe_spans.totals(run)
    llm = run["config"]["llm_config"]
    if got is None or not llm.get("arch") or not got[0]:
        return None
    rows, busiest, _steps = got
    return busiest / (rows / shapes_mla_moe.experts_held(llm))
