"""Bytes and operations a decode step of a decoder with gated delta-rule
(KDA) layers, position-free latent attention layers and expert layers
(`model_type` `kimi_linear`, one chip's share of it) has to move, computed
from the shapes alone, in the manner of `shapes_mla_moe.py` and
`shapes_swa_moe.py`. `llm` is a configuration's `llm_config`: the sizes as
run plus `arch`, the published keys.

The least a step can do: read every weight this chip holds once, except the
embedding table (a lookup of `batch` rows); read and write the state of
EVERY slot once in each KDA layer (S in float32 and the filters' tail: a
state cannot stay on the chip between steps, and a free slot's is stepped
like any other; the pending correction the program keeps beside S is its
own device and is not counted); and read every latent row that is visible to a live slot
once in each MLA layer, `kv_lora_rank + qk_rope_head_dim` values. A second
read of S inside a step, the latent walk beyond a slot's own rows and the
zeros that widen a latent row to its tiles are what the roofline share
exposes, so none of it is counted. Of the held experts, each one a step
TOUCHED is read once (an expert no row was routed to need not be read: a
step of 64 rows leaves about two of the 16 without one, a deployment's
step, 1024 rows from 16 chips, none); every held expert where the program
does not say how many its steps touched (`benchmark/moe_spans.py`
`touched_per_step`).
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _arch(llm: dict) -> dict:
    return llm["arch"]


def _lin(llm: dict) -> dict:
    return llm["arch"]["linear_attn_config"]


def is_kda(llm: dict) -> bool:
    """A configuration whose `arch` names gated delta-rule layers."""
    return "linear_attn_config" in (llm.get("arch") or {})


def mixers(llm: dict) -> list[str]:
    """`kda` or `mla` for each layer that is run, in order (the published
    lists count from 1)."""
    kda = set(_lin(llm)["kda_layers"])
    return ["kda" if i in kda else "mla"
            for i in range(1, llm["n_layers"] + 1)]


def kda_layers(llm: dict) -> int:
    return mixers(llm).count("kda")


def mla_layers(llm: dict) -> int:
    return mixers(llm).count("mla")


def expert_layers(llm: dict) -> int:
    return llm["n_layers"] - _arch(llm)["first_k_dense_replace"]


def experts_held(llm: dict) -> int:
    return llm.get("experts_held") or _arch(llm)["num_experts"]


def kda_params(llm: dict) -> int:
    d, lin = llm["d_model"], _lin(llm)
    h, dk, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    return (3 * d * h * dk + h * dk * d  # W_q, W_k, W_v and W_o
            + 2 * (d * dk + dk * h * dk)  # f_a, f_b and g_a, g_b
            + d * h  # W_beta
            + 3 * taps * h * dk  # the three filters
            + h + h * dk  # A_log, dt_bias
            + dk)  # the head norm's gain


def mla_params(llm: dict) -> int:
    a, d, h = _arch(llm), llm["d_model"], llm["n_heads"]
    rank, nope, rot, v = (a["kv_lora_rank"], a["qk_nope_head_dim"],
                          a["qk_rope_head_dim"], a["v_head_dim"])
    return (d * h * (nope + rot)  # W_q (q_lora_rank null)
            + d * (rank + rot) + rank  # W_kva and the latent's norm
            + h * rank * (nope + v)  # the two halves of W_kvb
            + h * v * d)  # W_o


def expert_params(llm: dict) -> int:
    """One routed expert (a shared expert is of the same width)."""
    return 3 * llm["d_model"] * _arch(llm)["moe_intermediate_size"]


def layer_params(llm: dict, i: int) -> dict:
    """Parameters of layer i held here, by part."""
    a, d = _arch(llm), llm["d_model"]
    kind = mixers(llm)[i]
    out = {kind: kda_params(llm) if kind == "kda" else mla_params(llm),
           "norms": 2 * d}
    if i < a["first_k_dense_replace"]:
        out["dense_ffn"] = 3 * d * a["intermediate_size"]
    else:
        out["router"] = d * a["num_experts"] + a["num_experts"]
        out["shared_expert"] = a["num_shared_experts"] * expert_params(llm)
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


def state_slot_bytes(llm: dict) -> int:
    """One slot's state in one KDA layer as the layer's equations keep it: S
    in float32 and the last `kernel - 1` inputs of the three filters in the
    cache's dtype. What a step has to read and write."""
    lin = _lin(llm)
    h, dk = lin["num_heads"], lin["head_dim"]
    return (h * dk * dk * 4 + (lin["short_conv_kernel_size"] - 1) * 3 * h * dk
            * _BYTES[llm["dtype"]])


def pending_slot_bytes(llm: dict) -> int:
    """What the program keeps beside it, so that a step reads S once: the
    last token's correction (alpha, k, u: `models/kda.py` `step`) in
    float32. Held, read and written, but no part of the least a step must
    move."""
    lin = _lin(llm)
    return 3 * lin["num_heads"] * lin["head_dim"] * 4


def latent_row_bytes(llm: dict) -> int:
    """One position's latent of one MLA layer, as the model defines it."""
    a = _arch(llm)
    return (a["kv_lora_rank"] + a["qk_rope_head_dim"]) * _BYTES[llm["dtype"]]


def cache_bytes(llm: dict, slots: int, row_values: int = 0) -> dict:
    """Bytes of the cache by kind of leaf; `row_values` is the width a
    latent row is held at (0: its own)."""
    row = (row_values * _BYTES[llm["dtype"]] if row_values
           else latent_row_bytes(llm))
    return {"state": kda_layers(llm) * slots
            * (state_slot_bytes(llm) + pending_slot_bytes(llm)),
            "full": mla_layers(llm) * slots * llm["max_seq"] * row}


def decode_step_state_bytes(llm: dict, slots: int) -> int:
    """Every slot's state read and written once in each KDA layer."""
    return 2 * kda_layers(llm) * slots * state_slot_bytes(llm)


def decode_step_flops(llm: dict, batch: int, latent_rows: float,
                      expert_rows: float) -> float:
    """Operations of one decode step. 2 per weight per sequence for
    everything every sequence passes through (mixers' matrices, dense
    layer, router, shared expert, head); 2 per weight of one expert per row
    routed to a held expert (`expert_rows` a step, summed over the expert
    layers); the recurrence: per slot, head and entry of S a decay, two
    products into the sums over dk and the correction (8); and the latent
    attention: per head and visible row, the score and the weighted sum
    over the latent's values."""
    a, lin, h = _arch(llm), _lin(llm), llm["n_heads"]
    parts = param_count(llm)
    through_all = sum(v for k, v in parts.items()
                      if k not in ("embedding", "routed_experts"))
    recur = (8.0 * kda_layers(llm) * batch * lin["num_heads"]
             * lin["head_dim"] ** 2)
    attend = (2.0 * h * (2 * a["kv_lora_rank"] + a["qk_rope_head_dim"])
              * mla_layers(llm) * latent_rows)
    return (2.0 * through_all * batch
            + 2.0 * expert_params(llm) * expert_rows + recur + attend)


def expected_expert_rows(llm: dict, batch: int) -> float:
    """Rows a step routes to held experts, over all expert layers, were the
    routing uniform: batch x experts per token x held / published."""
    a = _arch(llm)
    return (expert_layers(llm) * batch * a["num_experts_per_token"]
            * experts_held(llm) / a["num_experts"])


def decode_step_min_seconds(llm: dict, batch: int, latent_rows: float,
                            peak: dict, expert_rows: float | None = None,
                            touched: float | None = None) -> dict:
    """The least time the chip could take for one decode step, which of its
    two limits sets it, the bytes by part, and the experts counted as read
    beside those held. `latent_rows` is the rows visible to the live slots,
    summed over them."""
    if expert_rows is None:
        expert_rows = expected_expert_rows(llm, batch)
    weights = decode_step_weight_bytes(llm, touched)
    parts = {"experts": weights["routed_experts"],
             "state": decode_step_state_bytes(llm, batch),
             "kda_matrices": weights["kda"],
             "mla_matrices": weights["mla"],
             "latent_rows": mla_layers(llm) * latent_rows
             * latent_row_bytes(llm),
             "rest": sum(v for k, v in weights.items()
                         if k not in ("routed_experts", "kda", "mla"))}
    nbytes = sum(parts.values())
    flops = decode_step_flops(llm, batch, latent_rows, expert_rows)
    t_bw = nbytes / peak["hbm_bytes_per_s"]
    t_fl = flops / peak["bf16_flops_per_s"]
    held = expert_layers(llm) * experts_held(llm)
    return {"seconds": max(t_bw, t_fl), "bytes": nbytes, "flops": flops,
            "bound": "bandwidth" if t_bw >= t_fl else "compute",
            "parts": parts, "held": held,
            "touched": held if touched is None else touched}
