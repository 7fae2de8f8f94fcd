"""`shapes_swa_moe.py` against ISSUE 32's arithmetic for the cut
`trinity-mini-ep8-16l` (ISSUE 32's step-down (b): layers 0-15), and against the parameters the program really makes
(shapes only: nothing is computed)."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import peaks, shapes_swa_moe as sh  # noqa: E402

LLM = json.load(open(os.path.join(
    ROOT, "benchmark/configs/trinity-mini-ep8-16l.json")))["llm_config"]


def test_parameter_counts_are_the_issues():
    parts = sh.param_count(LLM)
    # 2048x4096 (q) + 2 x 2048x512 (k, v) + 2048x4096 (gate) + 4096x2048 (o)
    # = 27.26 M, and the two head norms' 256 gains
    assert sh.attention_params(LLM) == 27_262_976 + 256
    assert sh.expert_params(LLM) == 6_291_456  # 6.29 M an expert
    assert parts["dense_ffn"] == 2 * 3 * 2048 * 6144
    assert parts["routed_experts"] == 14 * 16 * 6_291_456
    assert parts["shared_expert"] == 14 * 6_291_456
    assert parts["embedding"] == parts["head"] == 25024 * 2048
    assert round(sum(parts.values()) / 1e9, 2) == 2.12
    assert (sh.full_layers(LLM), sh.window_layers(LLM)) == (4, 12)
    assert sh.expert_layers(LLM) == 14 and sh.experts_held(LLM) == 16
    # all 32 layers, as published: the issue's 4.27 B
    whole = dict(LLM, n_layers=32)
    assert round(sum(sh.param_count(whole).values()) / 1e9, 2) == 4.27
    assert round(sum(sh.cache_bytes(whole, 16).values()) / 1e9, 2) == 3.76


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
    by_layer = {i: sum(s.size for s in jax.tree.leaves(shapes[f"layer_{i}"]))
                for i in (0, 15)}
    assert by_layer == {i: sum(sh.layer_params(LLM, i).values())
                        for i in (0, 15)}


def test_the_cache_is_two_kinds_of_leaf():
    got = sh.cache_bytes(LLM, 16)
    assert sh.cache_row_bytes(LLM) == 2048
    assert got == {"full": 4 * 16 * 8192 * 2048,
                   "window": 12 * 16 * 2048 * 2048}
    assert round(sum(got.values()) / 1e9, 2) == 1.88
    # a slot of 3,500 positions shows 3,500 rows a full layer, 2,048 a ring
    assert sh.visible_rows(LLM, [3500, 1000]) == (4500.0, 3048.0)


def test_a_decode_step_is_bound_by_its_weights_and_a_quarter_cache():
    full, window = sh.visible_rows(LLM, [3617] * 16)
    least = sh.decode_step_min_seconds(LLM, 16, full, window,
                                       peaks.peaks("TPU v5e"))
    assert least["bound"] == "bandwidth"
    share = {k: v / least["bytes"] for k, v in least["parts"].items()}
    assert round(share["routed_experts"], 2) == 0.52
    assert round(share["full_layer_rows"] + share["window_layer_rows"],
                 2) == 0.24
    assert 6.4e-3 < least["seconds"] < 6.8e-3  # ~5.4 GB at 819 GB/s
    assert sh.expected_expert_rows(LLM, 16) == 14 * 16.0
    # without rings every layer would show (and hold) the whole context
    no_rings = sh.decode_step_cache_bytes(LLM, full, full)
    assert sum(no_rings.values()) > 1.4 * (
        least["parts"]["full_layer_rows"] + least["parts"]["window_layer_rows"])


def test_a_step_counts_the_held_experts_it_touched():
    """ISSUE 45: 10.1 of 16 touched a layer take the least step from 6.60
    to 5.33 ms; every other part stays."""
    pk = peaks.peaks("TPU v5e")
    full, window = sh.visible_rows(LLM, [3617] * 16)
    held = sh.decode_step_min_seconds(LLM, 16, full, window, pk)
    got = sh.decode_step_min_seconds(LLM, 16, full, window, pk,
                                     touched=14 * 10.1)
    assert (held["held"], held["touched"], got["touched"]) == (
        224, 224, 14 * 10.1)
    assert round(held["seconds"] * 1e3, 2) == 6.60
    assert round(got["seconds"] * 1e3, 2) == 5.33
    assert abs(held["bytes"] - got["bytes"]
               - (224 - 14 * 10.1) * 2 * 6_291_456) < 1  # 1.04 GB, in floats
    assert {k: v for k, v in got["parts"].items()
            if k != "routed_experts"} == {
        k: v for k, v in held["parts"].items() if k != "routed_experts"}
    assert sh.decode_step_min_seconds(
        LLM, 16, full, window, pk, touched=224)["bytes"] == held["bytes"]
