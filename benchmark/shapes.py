"""Bytes and operations a step of the served decoder has to move, computed
from the shapes alone. The least a decode step can do is read every weight
once and the valid rows of the key/value cache once; everything above that
(padding of the head size, a second copy of the cache, the walk to max_seq)
is what the roofline share exposes, so none of it is counted here."""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def d_ff(d_model: int) -> int:
    """The SwiGLU width `ray_tpu.llm.engine.model_config` derives."""
    return int(d_model * 8 / 3) // 8 * 8


def param_count(llm: dict) -> int:
    """Parameters held by the served model (the output head is tied to the
    embedding, so the embedding counts once)."""
    d, v, n = llm["d_model"], llm["vocab_size"], llm["n_layers"]
    per_layer = 4 * d * d + 3 * d * d_ff(d) + 2 * d
    return v * d + n * per_layer + d


def decode_step_weight_bytes(llm: dict) -> int:
    """Weight bytes one decode step reads: every layer matrix and the output
    head (the tied embedding, read whole for the logits). The embedding
    lookup itself reads `batch` rows, which is nothing beside the rest."""
    return param_count(llm) * _BYTES[llm["dtype"]]


def decode_step_cache_bytes(llm: dict, valid_rows: float) -> float:
    """Bytes of key/value cache one decode step reads: for each layer, K and
    V of every valid row (summed over the batch), at the head size as
    published (96, not the 128 it is padded to on the chip)."""
    return (2 * llm["n_layers"] * valid_rows * llm["d_model"]
            * _BYTES[llm["dtype"]])


def decode_step_flops(llm: dict, batch: int, valid_rows: float) -> float:
    """Operations of one decode step: 2 per weight per sequence, plus the
    attention's QK^T and PV over the valid rows."""
    return (2.0 * param_count(llm) * batch
            + 4.0 * llm["n_layers"] * valid_rows * llm["d_model"])


def decode_step_min_seconds(llm: dict, batch: int, valid_rows: float,
                            peak: dict) -> dict:
    """The least time the chip could take for one decode step and which of
    its two limits sets it."""
    nbytes = (decode_step_weight_bytes(llm)
              + decode_step_cache_bytes(llm, valid_rows))
    t_bw = nbytes / peak["hbm_bytes_per_s"]
    t_fl = decode_step_flops(llm, batch, valid_rows) / peak["bf16_flops_per_s"]
    return {"seconds": max(t_bw, t_fl), "bytes": nbytes,
            "bound": "bandwidth" if t_bw >= t_fl else "compute"}
