"""Bytes and operations a decode step of a decoder with window and full
attention layers, grouped key/value heads, a gated attention and expert
layers (`model_type` `afmoe`, one chip's share of it) has to move, computed
from the shapes alone, in the manner of `shapes_mla_moe.py`. `llm` is a
configuration's `llm_config`: the sizes as run plus `arch`, the published
keys.

The least a step can do: read every weight this chip holds once, except the
embedding table (a lookup of `batch` rows), and every row of the cache that
is visible to a live slot once: its context in a full layer, at most the
window in a window layer, K and V of the key/value heads at the published
head size. The walk beyond a slot's own rows, and the KV-fold products the
program makes against rows of other key/value heads, are what the roofline
share exposes, so none of it is counted. Of the held experts, each one a step
TOUCHED is read once (an expert no row was routed to need not be read: a
step of 16 rows leaves a third of the 16 without one, where a deployment's
step, 128 rows from 8 chips, would leave few); every held expert where the
program does not say how many its steps touched (`benchmark/moe_spans.py`
`touched_per_step`).
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _arch(llm: dict) -> dict:
    return llm["arch"]


def layer_kinds(llm: dict) -> list[bool]:
    """True for each window layer that is run, in order."""
    kinds = _arch(llm)["layer_types"][:llm["n_layers"]]
    return [k == "sliding_attention" for k in kinds]


def window_layers(llm: dict) -> int:
    return sum(layer_kinds(llm))


def full_layers(llm: dict) -> int:
    return llm["n_layers"] - window_layers(llm)


def window_rows(llm: dict) -> int:
    return min(_arch(llm)["sliding_window"], llm["max_seq"])


def expert_layers(llm: dict) -> int:
    return llm["n_layers"] - _arch(llm)["num_dense_layers"]


def experts_held(llm: dict) -> int:
    return llm.get("experts_held") or _arch(llm)["num_experts"]


def attention_params(llm: dict) -> int:
    a, d, h = _arch(llm), llm["d_model"], llm["n_heads"]
    hd, kv = a["head_dim"], a["num_key_value_heads"]
    return (2 * d * h * hd  # W_q and the gate's W_g
            + 2 * d * kv * hd  # W_k, W_v
            + h * hd * d  # W_o
            + 2 * hd)  # the query and key norms' gains


def expert_params(llm: dict) -> int:
    """One routed expert (a shared expert is of the same width)."""
    return 3 * llm["d_model"] * _arch(llm)["moe_intermediate_size"]


def layer_params(llm: dict, i: int) -> dict:
    """Parameters of layer i held here, by part."""
    a, d = _arch(llm), llm["d_model"]
    out = {"attention": attention_params(llm), "norms": 4 * d}
    if i < a["num_dense_layers"]:
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


def cache_row_bytes(llm: dict) -> int:
    """K and V of one position of one layer."""
    a = _arch(llm)
    return (2 * a["num_key_value_heads"] * a["head_dim"]
            * _BYTES[llm["dtype"]])


def cache_bytes(llm: dict, slots: int) -> dict:
    """Bytes of the cache by kind of leaf: `max_seq` rows a slot in a full
    layer, a ring of the window's rows in a window layer."""
    row = cache_row_bytes(llm) * slots
    return {"full": full_layers(llm) * llm["max_seq"] * row,
            "window": window_layers(llm) * window_rows(llm) * row}


def visible_rows(llm: dict, contexts) -> tuple[float, float]:
    """(rows of a full layer, rows of a window layer) visible to live slots
    whose contexts are given: the sum of the contexts, and of each cut to
    the window."""
    w = window_rows(llm)
    return (float(sum(contexts)), float(sum(min(c, w) for c in contexts)))


def decode_step_cache_bytes(llm: dict, rows_full: float,
                            rows_window: float) -> dict:
    """Bytes of cache a step reads, by kind: each visible row (summed over
    the live slots) once in each layer of its kind."""
    row = cache_row_bytes(llm)
    return {"full_layer_rows": full_layers(llm) * rows_full * row,
            "window_layer_rows": window_layers(llm) * rows_window * row}


def decode_step_flops(llm: dict, batch: int, rows_full: float,
                      rows_window: float, expert_rows: float) -> float:
    """Operations of one decode step. 2 per weight per sequence for
    everything every sequence passes through (attention matrices, dense
    layers, router, shared expert, head); 2 per weight of one expert per
    row routed to a held expert (`expert_rows` a step, summed over the
    expert layers); and the attention: per query head and visible row, the
    score and the weighted sum over the head's dims."""
    a, h = _arch(llm), llm["n_heads"]
    parts = param_count(llm)
    through_all = sum(v for k, v in parts.items()
                      if k not in ("embedding", "routed_experts"))
    attend = 4.0 * h * a["head_dim"] * (full_layers(llm) * rows_full
                                        + window_layers(llm) * rows_window)
    return (2.0 * through_all * batch
            + 2.0 * expert_params(llm) * expert_rows + attend)


def expected_expert_rows(llm: dict, batch: int) -> float:
    """Rows a step routes to held experts, over all expert layers, were the
    routing uniform: batch x experts per token x held / published."""
    a = _arch(llm)
    return (expert_layers(llm) * batch * a["num_experts_per_tok"]
            * experts_held(llm) / a["num_experts"])


def decode_step_min_seconds(llm: dict, batch: int, rows_full: float,
                            rows_window: float, peak: dict,
                            expert_rows: float | None = None,
                            touched: float | None = None) -> dict:
    """The least time the chip could take for one decode step, which of its
    two limits sets it, the bytes by part, and the experts counted as read
    beside those held."""
    if expert_rows is None:
        expert_rows = expected_expert_rows(llm, batch)
    parts = dict(decode_step_weight_bytes(llm, touched))
    parts.update(decode_step_cache_bytes(llm, rows_full, rows_window))
    nbytes = sum(parts.values())
    flops = decode_step_flops(llm, batch, rows_full, rows_window, expert_rows)
    t_bw = nbytes / peak["hbm_bytes_per_s"]
    t_fl = flops / peak["bf16_flops_per_s"]
    held = expert_layers(llm) * experts_held(llm)
    return {"seconds": max(t_bw, t_fl), "bytes": nbytes, "flops": flops,
            "bound": "bandwidth" if t_bw >= t_fl else "compute",
            "parts": parts, "held": held,
            "touched": held if touched is None else touched}
