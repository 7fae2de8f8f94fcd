"""`shapes_scmoe.py` against ISSUE 42's arithmetic for the cut
`longcat-flash-ep32-4l`, and against the parameters the program really makes
(shapes only: nothing is computed)."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import peaks, shapes_scmoe as sh  # noqa: E402

LLM = json.load(open(os.path.join(
    ROOT, "benchmark/configs/longcat-flash-ep32-4l.json")))["llm_config"]


def test_parameter_counts_are_the_issues():
    assert sh.is_scmoe(LLM)
    assert sh.attention_params(LLM) == 90_572_800  # 90.57 M an attention
    assert sh.dense_ffn_params(LLM) == 226_492_416  # 226.49 M a SwiGLU
    assert sh.expert_params(LLM) == 37_748_736  # 37.75 M an expert
    layer = sh.layer_params(LLM)
    assert layer["router"] == 6144 * 768 + 768  # 4.72 M
    outside = sum(v for k, v in layer.items() if k != "routed_experts")
    assert round(outside / 1e6, 2) == 638.87
    assert round(sum(layer.values()) / 1e6, 1) == 1242.9
    parts = sh.param_count(LLM)
    assert parts["embedding"] == parts["head"] == 16384 * 6144
    assert round(sum(parts.values()) / 1e9, 3) == 5.173  # 10.35 GB in bf16
    assert (sh.expert_layers(LLM), sh.latent_leaves(LLM),
            sh.router_outputs(LLM)) == (4, 8, 768)


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
    # and the cache: two latent leaves a layer
    cache = jax.eval_shape(
        lambda p: net.apply({"params": p}, jnp.zeros((32, 1), jnp.int32),
                            positions=jnp.zeros((32, 1), jnp.int32),
                            decode=True, mutable=["cache"])[1]["cache"],
        shapes)
    leaves = jax.tree.leaves(cache)
    assert len(leaves) == sh.latent_leaves(LLM)
    assert {leaf.shape for leaf in leaves} == {
        (32, 4096, sh.cache_row_values(LLM))}


def test_a_decode_step_is_bound_by_its_weights_and_follows_the_touched():
    peak = peaks.peaks("TPU v5e")
    whole = sh.decode_step_min_seconds(LLM, 32, 32 * 700, peak)
    assert whole["bound"] == "bandwidth"
    assert (whole["held"], whole["touched"]) == (64, 64)
    assert round(whole["parts"]["routed_experts"] / 1e9, 2) == 4.83
    assert round(whole["parts"]["dense_ffn"] / 1e9, 2) == 3.62
    assert round(whole["parts"]["attention"] / 1e9, 2) == 1.45
    assert round(whole["parts"]["head"] / 1e9, 2) == 0.20
    weights = whole["bytes"] - whole["parts"]["latent_cache"]
    assert round(weights / 1e9, 2) == 10.14  # 12.4 ms at 819 GB/s
    assert whole["parts"]["latent_cache"] == 8 * 32 * 700 * 1152
    # uniform routing: 8 rows a step a layer, and 6.3 of 16 experts touched
    assert sh.expected_expert_rows(LLM, 32) == 4 * 8.0
    touched = sh.expected_touched(LLM, 32)
    assert 6.2 < touched / 4 < 6.4
    some = sh.decode_step_min_seconds(LLM, 32, 32 * 700, peak,
                                      touched=touched)
    assert some["touched"] == touched and some["bound"] == "bandwidth"
    saved = (64 - touched) * sh.expert_params(LLM) * 2
    assert abs(whole["bytes"] - some["bytes"] - saved) < 1.0
    assert 8.9e-3 < some["seconds"] < 9.2e-3 < 12.5e-3 < whole["seconds"]
