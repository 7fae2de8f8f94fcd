"""Bytes and operations a decode step of a decoder with its expert layer on
a shortcut (the LongCat-Flash block, one chip's share of it) has to move,
computed from the shapes alone, in the manner of `shapes_mla_moe.py`. `llm`
is a configuration's `llm_config`: the sizes as run plus `arch`, the
published keys.

A layer holds TWO latent attentions, TWO dense SwiGLUs, one router over
`n_routed_experts + zero_expert_num` outputs and the routed experts held
here; the identity experts have no weights and cost one multiply-add a
value. A layer keeps two latent cache leaves.

The least a step can do: read every weight outside the experts once, except
the embedding table (a lookup of `batch` rows); each held expert a step
TOUCHED once (an expert no row was routed to need not be read: with 32 rows
of 12 selections over 768 outputs, 16 held experts get 8 rows a step and 60%
of them none; the three older configurations' shapes count the same way
since PR 45); and the rows of each latent leaf visible to a live slot
once, `kv_lora_rank + qk_rope_head_dim` values a row (576, not the 640 its
tiles pad it to on the chip).
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def is_scmoe(llm: dict) -> bool:
    arch = llm.get("arch") or {}
    return "zero_expert_num" in arch and "n_routed_experts" in arch


def _arch(llm: dict) -> dict:
    return llm["arch"]


def expert_layers(llm: dict) -> int:
    """One expert layer a published layer, from the first on."""
    return llm["n_layers"]


def latent_leaves(llm: dict) -> int:
    """Two latent attentions a layer, a cache leaf each."""
    return 2 * llm["n_layers"]


def experts_held(llm: dict) -> int:
    return llm.get("experts_held") or _arch(llm)["n_routed_experts"]


def router_outputs(llm: dict) -> int:
    return _arch(llm)["n_routed_experts"] + _arch(llm)["zero_expert_num"]


def attention_params(llm: dict) -> int:
    """One of a layer's two latent attentions."""
    a, d, h = _arch(llm), llm["d_model"], llm["n_heads"]
    qk = a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
    return (d * a["q_lora_rank"] + a["q_lora_rank"]  # W_qa, its norm
            + a["q_lora_rank"] * h * qk  # W_qb
            + d * (a["kv_lora_rank"] + a["qk_rope_head_dim"])  # W_kva
            + a["kv_lora_rank"]  # its norm
            + a["kv_lora_rank"] * h * (a["qk_nope_head_dim"]
                                       + a["v_head_dim"])  # W_kvb
            + h * a["v_head_dim"] * d)  # W_o


def dense_ffn_params(llm: dict) -> int:
    """One of a layer's two dense SwiGLUs."""
    return 3 * llm["d_model"] * _arch(llm)["ffn_hidden_size"]


def expert_params(llm: dict) -> int:
    """One routed expert."""
    return 3 * llm["d_model"] * _arch(llm)["expert_ffn_hidden_size"]


def layer_params(llm: dict) -> dict:
    """Parameters of one layer held here, by part."""
    d = llm["d_model"]
    return {"attention": 2 * attention_params(llm),
            "dense_ffn": 2 * dense_ffn_params(llm),
            "router": d * router_outputs(llm) + router_outputs(llm),
            "norms": 4 * d,
            "routed_experts": experts_held(llm) * expert_params(llm)}


def param_count(llm: dict) -> dict:
    """Parameters this chip holds, by part (the embedding and the untied
    head apart)."""
    total = {k: v * llm["n_layers"] for k, v in layer_params(llm).items()}
    total["norms"] += llm["d_model"]  # the final norm
    total["embedding"] = llm["vocab_size"] * llm["d_model"]
    total["head"] = llm["vocab_size"] * llm["d_model"]
    return total


def cache_row_values(llm: dict) -> int:
    return _arch(llm)["kv_lora_rank"] + _arch(llm)["qk_rope_head_dim"]


def expected_expert_rows(llm: dict, batch: int) -> float:
    """Rows a step routes to held experts, over all expert layers, were the
    routing uniform over the router's outputs: batch x selections per token
    x held / outputs."""
    return (expert_layers(llm) * batch * _arch(llm)["moe_topk"]
            * experts_held(llm) / router_outputs(llm))


def expected_touched(llm: dict, batch: int) -> float:
    """Held experts a step routes at least one row to, over all expert
    layers, were the routing uniform."""
    miss = (1.0 - 1.0 / router_outputs(llm)) ** (
        batch * _arch(llm)["moe_topk"])
    return expert_layers(llm) * experts_held(llm) * (1.0 - miss)


def decode_step_weight_bytes(llm: dict, touched: float | None = None) -> dict:
    """Weight bytes one decode step has to read, by part: every held weight
    outside the experts once, the embedding table left out, and one expert's
    weights for each held expert a step touched (`touched`, summed over the
    expert layers; every held expert where None)."""
    size = _BYTES[llm["dtype"]]
    parts = {k: v * size for k, v in param_count(llm).items()
             if k not in ("embedding", "routed_experts")}
    if touched is None:
        touched = expert_layers(llm) * experts_held(llm)
    parts["routed_experts"] = touched * expert_params(llm) * size
    return parts


def decode_step_cache_bytes(llm: dict, valid_rows: float) -> float:
    """Bytes of latent cache a step reads: each row visible to a live slot
    (`valid_rows`, summed over the batch, of ONE leaf) once a leaf."""
    return (latent_leaves(llm) * valid_rows * cache_row_values(llm)
            * _BYTES[llm["dtype"]])


def decode_step_flops(llm: dict, batch: int, valid_rows: float,
                      expert_rows: float) -> float:
    """Operations of one decode step. 2 per weight per sequence for
    everything every sequence passes through (attentions, dense SwiGLUs,
    router, head); 2 per weight of one expert per row routed to a held
    expert (`expert_rows` a step, summed over the expert layers; an
    identity expert's d_model multiply-adds are not counted); and the
    attention in the latent space: per head, leaf and visible row the score
    over rank + rope values and the weighted sum over rank."""
    a, h = _arch(llm), llm["n_heads"]
    parts = param_count(llm)
    through_all = sum(v for k, v in parts.items()
                      if k not in ("embedding", "routed_experts"))
    latent = (2.0 * latent_leaves(llm) * valid_rows * h
              * (cache_row_values(llm) + a["kv_lora_rank"]))
    return (2.0 * through_all * batch
            + 2.0 * expert_params(llm) * expert_rows + latent)


def decode_step_min_seconds(llm: dict, batch: int, valid_rows: float,
                            peak: dict, expert_rows: float | None = None,
                            touched: float | None = None) -> dict:
    """The least time the chip could take for one decode step, which of its
    two limits sets it, the bytes by part, and the experts counted as read
    beside those held."""
    if expert_rows is None:
        expert_rows = expected_expert_rows(llm, batch)
    parts = dict(decode_step_weight_bytes(llm, touched))
    parts["latent_cache"] = decode_step_cache_bytes(llm, valid_rows)
    nbytes = sum(parts.values())
    flops = decode_step_flops(llm, batch, valid_rows, expert_rows)
    t_bw = nbytes / peak["hbm_bytes_per_s"]
    t_fl = flops / peak["bf16_flops_per_s"]
    held = expert_layers(llm) * experts_held(llm)
    return {"seconds": max(t_bw, t_fl), "bytes": nbytes, "flops": flops,
            "bound": "bandwidth" if t_bw >= t_fl else "compute",
            "parts": parts, "held": held,
            "touched": held if touched is None else touched}
