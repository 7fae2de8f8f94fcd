"""zero_expert_share — layer: model step (models/moe.py, counted in
`jit_chunk`).

Of the selections the decode steps' rows made, the share that fell on an
identity (zero-computation) expert: `moe_zero_picks / moe_picks` over the
window's chunks. With routing uniform over the router's outputs it is
identity experts / outputs (256 / 768 = 0.33); the real experts a token uses
are the rest of its selections, and they vary from token to token. Printed
beside it: the mean real experts a token, and the held experts a step
touched (`moe_touched` a step a layer). A program whose router has no
identity experts counts none of this: nothing is returned."""

from benchmark import engine_spans as es, scmoe_spans, shapes_scmoe


@es.never_raises
def read(run: dict):
    got = scmoe_spans.totals(run)
    llm = run["config"]["llm_config"]
    if got is None or not got["picks"] or not shapes_scmoe.is_scmoe(llm):
        return None
    share = got["zero_picks"] / got["picks"]
    layers = shapes_scmoe.expert_layers(llm)
    top_k = llm["arch"]["moe_topk"]
    batch = run["config"]["app_kwargs"]["max_batch"]
    print(f"zero_expert_share: {got['zero_picks']} of {got['picks']} "
          f"selections in {got['steps']} steps fell on an identity expert; "
          f"{top_k * (1 - share):.2f} real experts a token of {top_k} "
          f"selected; {got['touched'] / (got['steps'] * layers):.2f} of "
          f"{shapes_scmoe.experts_held(llm)} held experts touched a step a "
          f"layer (uniform routing would give "
          f"{shapes_scmoe.expected_touched(llm, batch) / layers:.2f})",
          flush=True)
    return share
