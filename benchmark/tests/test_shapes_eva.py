"""`shapes_eva.py` against ISSUE 49's arithmetic for the cut
`evabyte-pp4-8l` (8 of 32 layers, 16384 positions a slot), and against the
parameters the program really makes (shapes only: nothing is computed)."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import peaks, shapes_eva as sh  # noqa: E402

LLM = json.load(open(os.path.join(
    ROOT, "benchmark/configs/evabyte-pp4-8l.json")))["llm_config"]


def test_parameter_counts_are_the_issues():
    layer = sh.layer_params(LLM)
    assert layer == {"attention": 67_108_864, "pooling": 8_192,
                     "ffn": 135_266_304, "norms": 8_192}
    assert sum(layer.values()) == 202_391_552  # 404.8 MB in bf16
    parts = sh.param_count(LLM)
    assert parts["embedding"] == 320 * 4096  # 1.31 M
    assert parts["head"] == 8 * 320 * 4096  # 10.49 M: all 8 heads held
    assert round(sum(parts.values()) / 1e6) == 1631
    assert round(sum(parts.values()) * 2 / 1e9, 2) == 3.26
    whole = dict(LLM, n_layers=32)
    assert round(sum(sh.param_count(whole).values()) * 2 / 1e9, 2) == 12.98
    assert sh.is_eva(LLM) and not sh.is_eva({"arch": {"model_type": "afmoe"}})
    assert not sh.is_eva({"n_layers": 2})


def test_the_count_is_what_the_program_makes():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.engine import model_config
    from ray_tpu.models.transformer import Transformer

    net = Transformer(model_config(LLMConfig(**LLM)))
    shapes = jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    made = sum(s.size for s in jax.tree.leaves(shapes))
    assert made == sum(sh.param_count(LLM).values())
    assert sum(s.size for s in jax.tree.leaves(shapes["layer_3"])) == sum(
        sh.layer_params(LLM).values())
    assert shapes["lm_head"].shape == (4096, 8 * 320)
    assert {k: v.shape for k, v in shapes["layer_0"]["attn"].items()
            if k in ("mu", "phi")} == {"mu": (32, 128), "phi": (32, 128)}


def test_the_cache_is_two_kinds_of_leaf_in_every_layer():
    assert sh.cache_row_bytes(LLM) == 16_384
    assert (sh.window_rows(LLM), sh.chunk_rows(LLM)) == (2048, 1024)
    got = sh.cache_bytes(LLM, 16)
    assert got == {"window": 8 * 16 * 2048 * 16_384,
                   "chunks": 8 * 16 * 1024 * 16_384}
    assert round(sum(got.values()) / 1e9, 2) == 6.44
    # a slot a layer: 33.55 MB of window rows, 16.78 MB of summaries
    assert round(got["window"] / 128 / 1e6, 2) == 33.55
    assert round(got["chunks"] / 128 / 1e6, 2) == 16.78
    # full attention would hold 268 MB a slot a layer for the same context
    assert 16384 * 16_384 == 268_435_456
    # the step at position 5007 sees rows 0..911 of its window and the 256
    # summaries of the two windows behind it; at 2047 no summary yet
    assert sh.visible_rows(LLM, [5007, 2047]) == (912.0 + 2048.0, 256.0)
    assert sh.visible_rows(LLM, [2048]) == (1.0, 128.0)
    assert sh.visible_rows(LLM, [16383]) == (2048.0, 896.0)


def test_a_decode_step_is_bound_by_its_weights_and_its_two_leaves():
    """ISSUE 49 section 5: 16 slots near a context of 4,800 show about
    1,024 window rows and 250 summaries each: 2.7 GB of cache beside 3.26
    GB of weights, about 45% of 5.9 GB, 7.3 ms at 819 GB/s."""
    least = sh.decode_step_min_seconds(LLM, 16, 16 * 1024.0, 16 * 250.0,
                                       peaks.peaks("TPU v5e"))
    assert least["bound"] == "bandwidth"
    cache = least["parts"]["window_rows"] + least["parts"]["summary_rows"]
    assert round(cache / 1e9, 1) == 2.7
    assert round(cache / least["bytes"], 2) == 0.45
    assert 7.2e-3 < least["seconds"] < 7.4e-3
    # walking both leaves whole: 6.4 GB of cache, no step under 11.8 ms
    whole = sh.decode_step_min_seconds(LLM, 16, 16 * 2048.0, 16 * 1024.0,
                                       peaks.peaks("TPU v5e"))
    assert round((whole["parts"]["window_rows"]
                  + whole["parts"]["summary_rows"]) / 1e9, 1) == 6.4
    assert 11.7e-3 < whole["seconds"] < 11.9e-3
    assert least["flops"] < 0.1 * 197e12 * least["seconds"]
