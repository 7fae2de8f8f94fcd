"""Bytes and operations a decode step of a decoder with EVA attention
(`model_type` `evabyte`: a window of exact rows that starts over beside one
summary row for every chunk of positions behind it, both under one softmax;
`ray_tpu/models/eva.py`) has to move, computed from the shapes alone, in the
manner of `shapes_swa_moe.py`. `llm` is a configuration's `llm_config`: the
sizes as run plus `arch`, the published keys.

The least a step can do: read every weight this chip holds once, except the
embedding table (a lookup of `batch` rows), the head's unread columns among
them (`num_pred_heads` x vocabulary columns are HELD; a program that reads
head 0 alone does better than this count, by 9 MB of 3.26 GB), and every row
of the cache that is visible to a live slot once: the rows of its current
window so far, and one summary row for every chunk of every window before
it, K and V of all heads, in each layer. The walk beyond a slot's own rows is
what the roofline share exposes, so none of it is counted. The parameters
are counted leaf by leaf as `Transformer.init` makes them
(`benchmark/tests/test_shapes_eva.py` holds the two against each other).
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _arch(llm: dict) -> dict:
    return llm["arch"]


def is_eva(llm: dict) -> bool:
    """A configuration whose `arch` names EVA attention."""
    return (llm.get("arch") or {}).get("attention_class") == "eva"


def head_dim(llm: dict) -> int:
    return _arch(llm).get("head_dim") or llm["d_model"] // llm["n_heads"]


def window_rows(llm: dict) -> int:
    return min(_arch(llm)["window_size"], llm["max_seq"])


def chunk_rows(llm: dict) -> int:
    """Rows of a summaries leaf a slot: one a chunk of `max_seq`."""
    return max(1, llm["max_seq"] // _arch(llm)["chunk_size"])


def layer_params(llm: dict) -> dict:
    """Parameters of one layer, leaf by leaf as `Transformer.init` makes
    them: wq, wk, wv [d, H, D], wo [H, D, d], mu and phi [H, D], the
    SwiGLU's three matrices, the two norms' g."""
    d, h, hd = llm["d_model"], llm["n_heads"], head_dim(llm)
    return {"attention": 4 * d * h * hd, "pooling": 2 * h * hd,
            "ffn": 3 * d * _arch(llm)["intermediate_size"], "norms": 2 * d}


def param_count(llm: dict) -> dict:
    """Parameters this chip holds, by part (the embedding and the untied
    head of `num_pred_heads` x vocabulary columns apart)."""
    total = {k: v * llm["n_layers"] for k, v in layer_params(llm).items()}
    total["norms"] += llm["d_model"]  # the final norm
    total["embedding"] = llm["vocab_size"] * llm["d_model"]
    total["head"] = (_arch(llm).get("num_pred_heads", 1) * llm["vocab_size"]
                     * llm["d_model"])
    return total


def decode_step_weight_bytes(llm: dict) -> dict:
    """Weight bytes one decode step has to read, by part: every held weight
    once, the embedding table left out."""
    size = _BYTES[llm["dtype"]]
    return {k: v * size for k, v in param_count(llm).items()
            if k != "embedding"}


def cache_row_bytes(llm: dict) -> int:
    """K and V of one row (a position, or a chunk's summary) of one layer."""
    return 2 * llm["n_heads"] * head_dim(llm) * _BYTES[llm["dtype"]]


def cache_bytes(llm: dict, slots: int) -> dict:
    """Bytes of the cache by kind of leaf: the window's rows and one row a
    chunk of `max_seq`, a slot a layer."""
    row = cache_row_bytes(llm) * slots * llm["n_layers"]
    return {"window": window_rows(llm) * row, "chunks": chunk_rows(llm) * row}


def visible_rows(llm: dict, contexts) -> tuple[float, float]:
    """(window rows, summary rows) visible to the steps of live slots that
    are AT the positions given: p mod W + 1 of the window, W / C for each
    window before p's."""
    w, c = window_rows(llm), _arch(llm)["chunk_size"]
    return (float(sum(p % w + 1 for p in contexts)),
            float(sum(p // w * (w // c) for p in contexts)))


def decode_step_cache_bytes(llm: dict, rows_window: float,
                            rows_chunks: float) -> dict:
    """Bytes of cache a step reads, by kind: each visible row (summed over
    the live slots) once in each layer."""
    row = cache_row_bytes(llm) * llm["n_layers"]
    return {"window_rows": rows_window * row, "summary_rows": rows_chunks * row}


def decode_step_flops(llm: dict, batch: int, rows_window: float,
                      rows_chunks: float) -> float:
    """Operations of one decode step: 2 per weight per sequence for every
    matrix a sequence passes through (head 0's columns of the head), and
    the attention: per head and visible row of either leaf, the score and
    the weighted sum over the head's dims."""
    parts = param_count(llm)
    through = (parts["attention"] + parts["ffn"]
               + llm["vocab_size"] * llm["d_model"])
    attend = (4.0 * llm["n_heads"] * head_dim(llm) * llm["n_layers"]
              * (rows_window + rows_chunks))
    return 2.0 * through * batch + attend


def decode_step_min_seconds(llm: dict, batch: int, rows_window: float,
                            rows_chunks: float, peak: dict) -> dict:
    """The least time the chip could take for one decode step, which of its
    two limits sets it, and the bytes by part."""
    parts = dict(decode_step_weight_bytes(llm))
    parts.update(decode_step_cache_bytes(llm, rows_window, rows_chunks))
    nbytes = sum(parts.values())
    flops = decode_step_flops(llm, batch, rows_window, rows_chunks)
    t_bw = nbytes / peak["hbm_bytes_per_s"]
    t_fl = flops / peak["bf16_flops_per_s"]
    return {"seconds": max(t_bw, t_fl), "bytes": nbytes, "flops": flops,
            "bound": "bandwidth" if t_bw >= t_fl else "compute",
            "parts": parts}
