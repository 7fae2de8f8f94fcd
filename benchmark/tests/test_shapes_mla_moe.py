"""`shapes_mla_moe.py` against ISSUE 28's arithmetic for the cut
`kimi-k2-ep32-6l`, and against the parameters the program really makes
(shapes only: nothing is computed)."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import peaks, shapes_mla_moe as sh  # noqa: E402

LLM = json.load(open(os.path.join(
    ROOT, "benchmark/configs/kimi-k2-ep32-6l.json")))["llm_config"]


def test_parameter_counts_are_the_issues():
    parts = sh.param_count(LLM)
    assert sh.attention_params(LLM) == 101_124_096  # 101.1 M a layer
    assert sh.expert_params(LLM) == 44_040_192  # 44.04 M an expert
    assert parts["dense_ffn"] == 3 * 7168 * 18432
    assert parts["routed_experts"] == 5 * 12 * 44_040_192
    assert parts["embedding"] == parts["head"] == 20480 * 7168
    assert round(sum(parts.values()) / 1e9, 2) == 4.17


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


def test_a_decode_step_is_bound_by_its_weights():
    least = sh.decode_step_min_seconds(LLM, 32, 32 * 900,
                                       peaks.peaks("TPU v5e"))
    assert least["bound"] == "bandwidth"
    share = {k: v / least["bytes"] for k, v in least["parts"].items()}
    assert round(share["routed_experts"], 2) == 0.64
    assert round(share["attention"], 2) == 0.15
    assert round(share["dense_ffn"], 2) == 0.10
    assert 9.9e-3 < least["seconds"] < 10.3e-3  # ~8.25 GB at 819 GB/s
    assert sh.expected_expert_rows(LLM, 32) == 5 * 8.0


def test_a_step_counts_the_held_experts_it_touched():
    """ISSUE 45: at the cell's 35,200 latent rows, every held expert read is
    8,296,056,576 bytes and 10.13 ms; 6 of 12 a layer, 5.65 GB and 6.9 ms."""
    pk = peaks.peaks("TPU v5e")
    held = sh.decode_step_min_seconds(LLM, 32, 35200, pk, 40.5)
    assert (held["bytes"], held["held"], held["touched"]) == (
        8_296_056_576, 60, 60)
    assert round(held["seconds"] * 1e3, 2) == 10.13
    half = sh.decode_step_min_seconds(LLM, 32, 35200, pk, 40.5, touched=30)
    assert half["bytes"] == 8_296_056_576 - 30 * 2 * 44_040_192
    assert round(half["seconds"] * 1e3, 2) == 6.90
    assert (half["held"], half["touched"]) == (60, 30)
    # 5.8 to 6.1 of 12 a layer, the uniform draw's and a little more
    low, high = (sh.decode_step_min_seconds(LLM, 32, 35200, pk, 40.5,
                                            touched=5 * t)["seconds"]
                 for t in (5.8, 6.1))
    assert 6.79e-3 < low < 6.80e-3 and 6.95e-3 < high < 6.96e-3
    assert sh.decode_step_weight_bytes(LLM) == sh.decode_step_weight_bytes(
        LLM, touched=60)
