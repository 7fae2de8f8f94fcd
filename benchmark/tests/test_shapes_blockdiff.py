"""`shapes_blockdiff.py` against ISSUE 58's arithmetic for the cut
`sdar-30b-a3b-pp8-6l`, and against the parameters the program really makes
(shapes only: nothing is computed)."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import peaks, shapes_blockdiff as sh  # noqa: E402

CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark/configs/sdar-30b-a3b-pp8-6l.json")))
LLM = CONFIG["llm_config"]


def test_parameter_counts_are_the_issues():
    parts = sh.param_count(LLM)
    # 2048x4096 (q) + 2 x 2048x512 (k, v) + 4096x2048 (o) = 18.87 M, and the
    # two head norms' 256 gains
    assert sh.attention_params(LLM) == 18_874_368 + 256
    assert sh.expert_params(LLM) == 4_718_592  # 3 x 2048 x 768
    assert parts["routed_experts"] == 6 * 128 * 4_718_592
    assert parts["router"] == 6 * 262_144
    assert parts["embedding"] == parts["head"] == 151_936 * 2048
    assert round(sum(parts.values()) / 1e9, 3) == 4.361  # 8.72 GB in bf16
    assert sh.cache_row_bytes(LLM) == 2048
    assert round(sh.cache_bytes(LLM, 32) / 1e9, 2) == 0.81
    assert sh.expert_layers(LLM) == 6 and sh.experts_held(LLM) == 128
    assert sh.is_blockdiff(LLM) and sh.block_length(LLM) == 4
    # all 48 layers, as published: 30.5 B
    whole = dict(LLM, n_layers=48)
    assert round(sum(sh.param_count(whole).values()) / 1e9, 1) == 30.5


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
    assert sum(s.size for s in jax.tree.leaves(shapes)) == sum(
        sh.param_count(LLM).values())
    layer = sum(s.size for s in jax.tree.leaves(shapes["layer_5"]))
    assert layer == (sh.attention_params(LLM) + 2 * 2048 + 262_144
                     + 128 * sh.expert_params(LLM))


def test_a_forward_of_the_cell_is_bound_by_the_experts_bytes():
    """32 slots of 4 positions, 400 rows visible a slot: the issue's 7.3 GB
    of experts beside the head's 0.6 GB, 10.1 ms at 819 GB/s; on the experts
    a forward touched, fewer."""
    peak = peaks.peaks("TPU v5 lite")
    least = sh.forward_min_seconds(LLM, 32, 32 * 400.0, peak)
    assert least["bound"] == "bandwidth"
    assert round(least["parts"]["routed_experts"] / 1e9, 2) == 7.25
    assert round(least["parts"]["head"] / 1e9, 2) == 0.62
    assert least["parts"]["cache_rows"] == 6 * 32 * 400 * 2048
    assert least["parts"]["embedding"] == 32 * 4 * 2048 * 2
    assert 9.9e-3 < least["seconds"] < 10.3e-3
    # every row's 8 selections over 6 layers; the operations stay under the
    # bytes' time
    assert sh.expected_expert_rows(LLM, 32) == 6 * 32 * 4 * 8
    assert least["flops"] / peak["bf16_flops_per_s"] < least["seconds"]
    fewer = sh.forward_min_seconds(LLM, 32, 32 * 400.0, peak, touched=600.0)
    assert fewer["touched"] == 600.0 and fewer["held"] == 768
    assert fewer["seconds"] < least["seconds"]
