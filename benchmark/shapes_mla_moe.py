"""Bytes and operations a decode step of a latent-attention, expert-layer
decoder (the DeepSeek-V3 / Kimi K2 block, one chip's share of it) has to
move, computed from the shapes alone, in the manner of `shapes.py`. `llm` is
a configuration's `llm_config`: the sizes as run plus `arch`, the published
keys.

The least a step can do: read every weight this chip holds once, except the
embedding table (a lookup of `batch` rows), and the valid rows of the latent
cache once, `kv_lora_rank + qk_rope_head_dim` values a row a layer. The walk
to `max_seq`, a second read of the cache for the weighted sum and the padding
of a row to its tile are what the roofline share exposes, so none of it is
counted. Of the held experts, each one a step TOUCHED is read once (an expert
no row was routed to need not be read: a step of 32 rows leaves half of the
12 without one, where a deployment's step, 256 rows from 32 chips, would
leave few); every held expert where the program does not say how many its
steps touched (`benchmark/moe_spans.py` `touched_per_step`).
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _arch(llm: dict) -> dict:
    return llm["arch"]


def expert_layers(llm: dict) -> int:
    return llm["n_layers"] - _arch(llm)["first_k_dense_replace"]


def experts_held(llm: dict) -> int:
    return llm.get("experts_held") or _arch(llm)["n_routed_experts"]


def attention_params(llm: dict) -> int:
    a, d, h = _arch(llm), llm["d_model"], llm["n_heads"]
    qk = a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
    return (d * a["q_lora_rank"] + a["q_lora_rank"]  # W_qa, its norm
            + a["q_lora_rank"] * h * qk  # W_qb
            + d * (a["kv_lora_rank"] + a["qk_rope_head_dim"])  # W_kva
            + a["kv_lora_rank"]  # its norm
            + a["kv_lora_rank"] * h * (a["qk_nope_head_dim"]
                                       + a["v_head_dim"])  # W_kvb
            + h * a["v_head_dim"] * d)  # W_o


def expert_params(llm: dict) -> int:
    """One routed expert (the shared expert is of the same width)."""
    return 3 * llm["d_model"] * _arch(llm)["moe_intermediate_size"]


def layer_params(llm: dict, i: int) -> dict:
    """Parameters of layer i held here, by part."""
    a, d = _arch(llm), llm["d_model"]
    out = {"attention": attention_params(llm), "norms": 2 * d}
    if i < a["first_k_dense_replace"]:
        out["dense_ffn"] = 3 * d * a["intermediate_size"]
    else:
        out["router"] = d * a["n_routed_experts"] + a["n_routed_experts"]
        out["shared_expert"] = a["n_shared_experts"] * expert_params(llm)
        out["routed_experts"] = experts_held(llm) * expert_params(llm)
    return out


def param_count(llm: dict) -> dict:
    """Parameters this chip holds, by part (the embedding and the untied
    head apart)."""
    total: dict = {}
    for i in range(llm["n_layers"]):
        for k, v in layer_params(llm, i).items():
            total[k] = total.get(k, 0) + v
    total["norms"] += llm["d_model"]  # the final norm
    total["embedding"] = llm["vocab_size"] * llm["d_model"]
    total["head"] = llm["vocab_size"] * llm["d_model"]
    return total


def decode_step_weight_bytes(llm: dict, touched: float | None = None) -> dict:
    """Weight bytes one decode step has to read, by part: every held weight
    outside the routed experts once, the embedding table left out, and one
    expert's weights for each held expert a step touched (`touched`, summed
    over the expert layers; every held expert where None)."""
    size = _BYTES[llm["dtype"]]
    parts = {k: v * size for k, v in param_count(llm).items()
             if k != "embedding"}
    if touched is not None:
        parts["routed_experts"] = touched * expert_params(llm) * size
    return parts


def cache_row_values(llm: dict) -> int:
    return _arch(llm)["kv_lora_rank"] + _arch(llm)["qk_rope_head_dim"]


def decode_step_cache_bytes(llm: dict, valid_rows: float) -> float:
    """Bytes of latent cache a step reads: each valid row (summed over the
    batch) once a layer, at its published width (576, not the 640 its tiles
    pad it to on the chip)."""
    return (llm["n_layers"] * valid_rows * cache_row_values(llm)
            * _BYTES[llm["dtype"]])


def decode_step_flops(llm: dict, batch: int, valid_rows: float,
                      expert_rows: float) -> float:
    """Operations of one decode step. 2 per weight per sequence for
    everything every sequence passes through (attention matrices, dense
    layer, router, shared expert, head); 2 per weight of one expert per row
    routed to a held expert (`expert_rows` a step, summed over the expert
    layers); and the attention in the latent space: per head and valid row
    the score over rank + rope values and the weighted sum over rank."""
    a, h = _arch(llm), llm["n_heads"]
    parts = param_count(llm)
    through_all = sum(v for k, v in parts.items()
                      if k not in ("embedding", "routed_experts"))
    latent = (2.0 * llm["n_layers"] * valid_rows * h
              * (cache_row_values(llm) + a["kv_lora_rank"]))
    return (2.0 * through_all * batch
            + 2.0 * expert_params(llm) * expert_rows + latent)


def expected_expert_rows(llm: dict, batch: int) -> float:
    """Rows a step routes to held experts, over all expert layers, were the
    routing uniform: batch x experts per token x held / published."""
    a = _arch(llm)
    return (expert_layers(llm) * batch * a["num_experts_per_tok"]
            * experts_held(llm) / a["n_routed_experts"])


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
