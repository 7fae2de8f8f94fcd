"""ouro_cache_share — layer: model step (models/transformer.py's loop over
the stack: what the traffic gives the mechanism).

Of the least bytes a decode step has to move (`benchmark/shapes_loop.py`:
every layer weight once a pass, the head once, each visible cache row once a
layer a pass), the share that are CACHE ROWS of the passes' leaves, in %,
over the chunks dispatched in the window, each weighted by its steps. Three
of every four of those rows, like three of every four readings of a layer's
weights, exist only because of the loop. Short contexts leave the step to
the weights and long ones to the rows: a later change of the mix that moves
the work from one to the other cannot pass unseen."""

from benchmark import engine_spans as es, loop_spans, shapes_loop


@es.never_raises
def read(run: dict):
    found = loop_spans.chunks(run)
    if not found:
        return None
    llm = run["config"]["llm_config"]
    visible = loop_spans.rows_a_step(found, "kv_live_full")
    weights = sum(shapes_loop.decode_step_weight_bytes(llm).values())
    rows = sum(shapes_loop.decode_step_cache_bytes(llm, visible).values())
    steps = sum(c["tokens"] for c in found)
    active = sum(loop_spans.slot_steps(c) for c in found) / steps
    print(f"ouro_cache_share: a step's least bytes are {weights / 1e9:.3f} "
          f"GB of weights and {rows / 1e9:.3f} GB of cache rows "
          f"({active:.2f} slots active, {visible / active:.0f} rows visible "
          f"a slot a leaf, {shapes_loop.passes(llm)} passes)", flush=True)
    return 100.0 * rows / (weights + rows)
