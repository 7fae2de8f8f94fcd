"""Bytes and operations one FORWARD of a block-diffusion decoder (`model_type`
`sdar_moe`: grouped key/value heads, query/key norms, every layer's
feed-forward an expert layer, generation by diffusion over blocks of L
positions) has to move, computed from the shapes alone, in the manner of
`shapes_swa_moe.py`. `llm` is a configuration's `llm_config`: the sizes as run
plus `arch`, the published keys and the generation's five values.

A forward carries L positions of every slot of the batch. The least it can
do: read every weight this chip holds outside the routed experts and the
embedding table once; the embedding's rows of the forward's inputs (batch x
L); one expert's weights for each expert the forward TOUCHED (an expert no
row was routed to need not be read; every expert where the program does not
say how many its forwards touched: `benchmark/moe_spans.py`
`touched_per_step`); and the rows [0, committed + L) of K and V of each live
slot ONCE a forward, not once a query: all L queries of a slot read the same
rows. The operations are those of batch x L rows through everything every row
passes through, of the rows routed to the experts, and of L queries a slot
against its visible rows. The products the program makes against rows of
other key/value heads, the walk beyond a slot's own rows and a dense arm's
products over experts that got no row are what the roofline share exposes,
so none of it is counted.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _arch(llm: dict) -> dict:
    return llm["arch"]


def block_length(llm: dict) -> int:
    return int(_arch(llm)["block_length"])


def is_blockdiff(llm: dict) -> bool:
    """A configuration whose `arch` generates by diffusion over blocks."""
    arch = llm.get("arch") or {}
    return "block_length" in arch and "denoising_steps" in arch


def expert_layers(llm: dict) -> int:
    return llm["n_layers"]  # decoder_sparse_step 1, mlp_only_layers []


def experts_held(llm: dict) -> int:
    return llm.get("experts_held") or _arch(llm)["num_experts"]


def attention_params(llm: dict) -> int:
    a, d, h = _arch(llm), llm["d_model"], llm["n_heads"]
    hd, kv = a["head_dim"], a["num_key_value_heads"]
    return (d * h * hd  # W_q
            + 2 * d * kv * hd  # W_k, W_v
            + h * hd * d  # W_o
            + 2 * hd)  # ONE query and one key norm weight for all heads


def expert_params(llm: dict) -> int:
    return 3 * llm["d_model"] * _arch(llm)["moe_intermediate_size"]


def param_count(llm: dict) -> dict:
    """Parameters this chip holds, by part (the embedding and the untied
    head apart)."""
    d, n = llm["d_model"], llm["n_layers"]
    return {"attention": n * attention_params(llm),
            "norms": n * 2 * d + d,  # two a layer and the final one
            "router": n * d * _arch(llm)["num_experts"],
            "routed_experts": n * experts_held(llm) * expert_params(llm),
            "embedding": llm["vocab_size"] * d,
            "head": llm["vocab_size"] * d}


def forward_weight_bytes(llm: dict, batch: int,
                         touched: float | None = None) -> dict:
    """Weight bytes one forward has to read, by part: every held weight
    outside the routed experts once, of the embedding the forward's batch x
    L input rows, and one expert's weights for each expert a forward touched
    (`touched`, summed over the expert layers; every held expert where
    None)."""
    size = _BYTES[llm["dtype"]]
    parts = {k: v * size for k, v in param_count(llm).items()}
    parts["embedding"] = batch * block_length(llm) * llm["d_model"] * size
    if touched is not None:
        parts["routed_experts"] = touched * expert_params(llm) * size
    return parts


def cache_row_bytes(llm: dict) -> int:
    """K and V of one position of one layer."""
    a = _arch(llm)
    return (2 * a["num_key_value_heads"] * a["head_dim"]
            * _BYTES[llm["dtype"]])


def cache_bytes(llm: dict, slots: int) -> int:
    return llm["n_layers"] * slots * llm["max_seq"] * cache_row_bytes(llm)


def forward_flops(llm: dict, batch: int, rows: float,
                  expert_rows: float) -> float:
    """Operations of one forward. 2 per weight per ROW (batch x L of them)
    for everything every row passes through (attention matrices, router,
    head); 2 per weight of one expert per row routed to a held expert
    (`expert_rows` a forward, summed over the expert layers); and the
    attention: per query head, query and visible row (`rows`, summed over the
    live slots, each seen by the slot's L queries), the score and the
    weighted sum over the head's dims."""
    a, size = _arch(llm), block_length(llm)
    parts = param_count(llm)
    through_all = sum(v for k, v in parts.items()
                      if k not in ("embedding", "routed_experts"))
    attend = (4.0 * llm["n_heads"] * a["head_dim"] * llm["n_layers"]
              * rows * size)
    return (2.0 * through_all * batch * size
            + 2.0 * expert_params(llm) * expert_rows + attend)


def expected_expert_rows(llm: dict, batch: int) -> float:
    """Rows a forward routes to held experts, over all expert layers, were
    the routing uniform: batch x L x experts per token x held / published."""
    a = _arch(llm)
    return (expert_layers(llm) * batch * block_length(llm)
            * a["num_experts_per_tok"] * experts_held(llm) / a["num_experts"])


def forward_min_seconds(llm: dict, batch: int, rows: float, peak: dict,
                        expert_rows: float | None = None,
                        touched: float | None = None) -> dict:
    """The least time the chip could take for one forward of `batch` slots
    whose live ones show `rows` cache rows in all (their open blocks'
    among them), which of its two limits sets it, the bytes by part, and the
    experts counted as read beside those held."""
    if expert_rows is None:
        expert_rows = expected_expert_rows(llm, batch)
    parts = forward_weight_bytes(llm, batch, touched)
    parts["cache_rows"] = llm["n_layers"] * rows * cache_row_bytes(llm)
    nbytes = sum(parts.values())
    flops = forward_flops(llm, batch, rows, expert_rows)
    t_bw = nbytes / peak["hbm_bytes_per_s"]
    t_fl = flops / peak["bf16_flops_per_s"]
    held = expert_layers(llm) * experts_held(llm)
    return {"seconds": max(t_bw, t_fl), "bytes": nbytes, "flops": flops,
            "bound": "bandwidth" if t_bw >= t_fl else "compute",
            "parts": parts, "held": held,
            "touched": held if touched is None else touched}
