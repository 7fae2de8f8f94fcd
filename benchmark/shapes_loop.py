"""Bytes and operations a decode step of a LOOPED decoder (`model_type`
`ouro`: the whole stack of layers applied `total_ut_steps` times to every
token with one set of weights, a pair of cache leaves a layer FOR EACH PASS;
`ray_tpu/models/transformer.py` `Transformer.__call__`) has to move, computed
from the shapes alone, in the manner of `shapes_eva.py`. `llm` is a
configuration's `llm_config`: the sizes as run plus `arch`, the published
keys.

The least a step can do: read every layer's weights once A PASS, the head,
the final norm and the gate once, nothing of the embedding table (a lookup
of `batch` rows), and every row of the cache that is visible to a live slot
once: K and V of all heads, in each layer, in each pass's own leaves. Why a
weight counts `total_ut_steps` times: pass t + 1 of a token needs pass t
WHOLE (the last layer's output, normed, is the first layer's input), so
between two readings of one matrix lie all the other layers' weights, 411 MB
at the benchmarked cut and 2.47 GB at the published depth, many times the
chip's on-chip memory: nothing keeps a matrix there from one pass to the
next. (Batching the passes of DIFFERENT tokens through a layer would, as
a pipeline does; within one decode step there is one token a slot.) The walk
beyond a slot's own rows is what the roofline share exposes, so none of it
is counted. The parameters are counted leaf by leaf as `Transformer.init`
makes them (`benchmark/tests/test_shapes_loop.py` holds the two against
each other).
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _arch(llm: dict) -> dict:
    return llm.get("arch") or {}


def passes(llm: dict) -> int:
    """Times the stack is applied to a token; 1 for a model without the
    key."""
    return int(_arch(llm).get("total_ut_steps") or 1)


def is_looped(llm: dict) -> bool:
    """A configuration whose `arch` runs its layers more than once."""
    return passes(llm) > 1


def head_dim(llm: dict) -> int:
    return _arch(llm).get("head_dim") or llm["d_model"] // llm["n_heads"]


def kv_heads(llm: dict) -> int:
    return _arch(llm).get("num_key_value_heads") or llm["n_heads"]


def layer_params(llm: dict) -> dict:
    """Parameters of one layer, leaf by leaf as `Transformer.init` makes
    them: wq [d, H, D], wk and wv [d, KV, D], wo [H, D, d], the SwiGLU's
    three matrices, the four norms' g."""
    d, hd = llm["d_model"], head_dim(llm)
    return {"attention": 2 * d * hd * (llm["n_heads"] + kv_heads(llm)),
            "ffn": 3 * d * _arch(llm)["intermediate_size"], "norms": 4 * d}


def param_count(llm: dict) -> dict:
    """Parameters this chip holds, by part: the layers' (held ONCE, whatever
    the passes), the final norm with the gate (a kernel [d] and a bias),
    the embedding and the untied head apart."""
    total = {k: v * llm["n_layers"] for k, v in layer_params(llm).items()}
    total["loop_end"] = llm["d_model"] + llm["d_model"] + 1
    total["embedding"] = llm["vocab_size"] * llm["d_model"]
    total["head"] = llm["vocab_size"] * llm["d_model"]
    return total


def decode_step_weight_bytes(llm: dict) -> dict:
    """Weight bytes one decode step has to read, by part: every layer's
    weights once a pass, the head, the final norm and the gate once, the
    embedding table left out."""
    size, held = _BYTES[llm["dtype"]], param_count(llm)
    layers = (held["attention"] + held["ffn"] + held["norms"]) * size
    return {"layers_first_pass": layers,
            "layers_later_passes": layers * (passes(llm) - 1),
            "head": held["head"] * size, "loop_end": held["loop_end"] * size}


def cache_row_bytes(llm: dict) -> int:
    """K and V of one position of one layer of one pass."""
    return 2 * kv_heads(llm) * head_dim(llm) * _BYTES[llm["dtype"]]


def cache_bytes(llm: dict, slots: int) -> int:
    """Bytes of the whole cache: `max_seq` rows a slot a layer A PASS."""
    return (cache_row_bytes(llm) * llm["max_seq"] * slots * llm["n_layers"]
            * passes(llm))


def decode_step_cache_bytes(llm: dict, visible_rows: float) -> dict:
    """Bytes of cache a step reads: each row visible in ONE leaf pair
    (summed over the live slots) once in each layer in each pass."""
    row = cache_row_bytes(llm) * llm["n_layers"]
    return {"cache_rows_first_pass": visible_rows * row,
            "cache_rows_later_passes": visible_rows * row * (passes(llm) - 1)}


def decode_step_flops(llm: dict, batch: int, visible_rows: float) -> float:
    """Operations of one decode step: 2 per weight per sequence for every
    matrix a sequence passes through (a layer's once a pass, the head
    once), and the attention: per head and visible row, the score and the
    weighted sum over the head's dims, in each layer in each pass."""
    parts = param_count(llm)
    through = ((parts["attention"] + parts["ffn"]) * passes(llm)
               + parts["head"])
    attend = (4.0 * llm["n_heads"] * head_dim(llm) * llm["n_layers"]
              * passes(llm) * visible_rows)
    return 2.0 * through * batch + attend


def decode_step_min_seconds(llm: dict, batch: int, visible_rows: float,
                            peak: dict) -> dict:
    """The least time the chip could take for one decode step, which of its
    two limits sets it, and the bytes by part."""
    parts = dict(decode_step_weight_bytes(llm))
    parts.update(decode_step_cache_bytes(llm, visible_rows))
    nbytes = sum(parts.values())
    flops = decode_step_flops(llm, batch, visible_rows)
    t_bw = nbytes / peak["hbm_bytes_per_s"]
    t_fl = flops / peak["bf16_flops_per_s"]
    return {"seconds": max(t_bw, t_fl), "bytes": nbytes, "flops": flops,
            "bound": "bandwidth" if t_bw >= t_fl else "compute",
            "parts": parts}
