"""`shapes_loop.py` against ISSUE 53's arithmetic for the cut `ouro-2.6b-8l`
(8 of 48 layers run 4 times a token, 2048 positions a slot), and against the
parameters and the cache the program really makes (shapes only: nothing is
computed)."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import peaks, shapes_loop as sh  # noqa: E402

LLM = json.load(open(os.path.join(
    ROOT, "benchmark/configs/ouro-2.6b-8l.json")))["llm_config"]


def test_parameter_counts_are_the_issues():
    layer = sh.layer_params(LLM)
    assert layer == {"attention": 16_777_216, "ffn": 34_603_008,
                     "norms": 8_192}
    assert sum(layer.values()) == 51_388_416  # 102.8 MB in bf16
    parts = sh.param_count(LLM)
    assert parts["embedding"] == parts["head"] == 100_663_296
    assert parts["loop_end"] == 2_048 + 2_049  # the final norm, the gate
    assert sum(parts.values()) == 612_438_017
    assert round(sum(parts.values()) * 2 / 1e9, 3) == 1.225
    # the WHOLE model fits one chip: 48 layers are 5.34 GB
    whole = dict(LLM, n_layers=48)
    assert round(sum(sh.param_count(whole).values()) / 1e6) == 2668
    assert round(sum(sh.param_count(whole).values()) * 2 / 1e9, 2) == 5.34
    assert sh.passes(LLM) == 4 and sh.is_looped(LLM)
    assert not sh.is_looped({"arch": {"model_type": "afmoe"}})
    assert not sh.is_looped({"n_layers": 2})
    assert not sh.is_looped({"arch": {"total_ut_steps": 1}})


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
    assert made == sum(sh.param_count(LLM).values())  # each layer ONCE
    assert sum(s.size for s in jax.tree.leaves(shapes["layer_3"])) == sum(
        sh.layer_params(LLM).values())
    assert sum(s.size for s in jax.tree.leaves(
        (shapes["final_norm"], shapes["exit_gate"]))) == sh.param_count(
        LLM)["loop_end"]
    assert shapes["lm_head"].shape == (2048, 49152)
    # and the cache: a K and V pair a layer FOR EACH PASS
    toks = jnp.zeros((16, 1), jnp.int32)
    cache = jax.eval_shape(
        lambda p: net.apply({"params": p}, toks, positions=toks, decode=True,
                            mutable=["cache"])[1]["cache"], shapes)
    leaves = jax.tree.leaves(cache)
    assert len(leaves) == 2 * 4 * 8
    assert sum(leaf.size * leaf.dtype.itemsize
               for leaf in leaves) == sh.cache_bytes(LLM, 16)


def test_the_cache_is_four_times_its_depth():
    assert sh.cache_row_bytes(LLM) == 8_192
    assert sh.cache_bytes(LLM, 16) == 8_589_934_592  # 8.59 GB
    # a slot: 16.78 MB a leaf pair, 536.9 MB over 32 pairs
    assert round(sh.cache_bytes(LLM, 1) / 32 / 1e6, 2) == 16.78
    assert round(sh.cache_bytes(LLM, 1) / 1e6, 1) == 536.9
    # a position of the whole model: 1.573 MB, four times Phi-3-mini's
    assert round(sh.cache_bytes(dict(LLM, n_layers=48, max_seq=1), 1)
                 / 1e6, 3) == 1.573


def test_a_decode_step_is_bound_by_bytes_three_quarters_of_them_the_loops():
    """ISSUE 53 section 5: 16 slots at a mean context near 620 rows: 3.29 GB
    of layer weights over four passes, 0.20 GB of head, 2.60 GB of cache
    rows: 6.09 GB, 7.4 ms at 819 GB/s, against 56-58 GFLOP (0.3 ms)."""
    least = sh.decode_step_min_seconds(LLM, 16, 16 * 620.0,
                                       peaks.peaks("TPU v5e"))
    parts = least["parts"]
    assert least["bound"] == "bandwidth"
    layers = parts["layers_first_pass"] + parts["layers_later_passes"]
    rows = parts["cache_rows_first_pass"] + parts["cache_rows_later_passes"]
    assert round(layers / 1e9, 2) == 3.29
    assert round(parts["head"] / 1e9, 2) == 0.20
    assert round(rows / 1e9, 2) == 2.60
    assert round(least["bytes"] / 1e9, 2) == 6.09
    assert 7.4e-3 < least["seconds"] < 7.5e-3
    assert round(rows / least["bytes"], 2) == 0.43
    # the second to fourth reading of every weight and three of every four
    # leaves exist only because of the loop
    loops = parts["layers_later_passes"] + parts["cache_rows_later_passes"]
    assert parts["layers_later_passes"] == 3 * parts["layers_first_pass"]
    assert round(loops / least["bytes"], 2) == 0.73
    assert least["flops"] < 0.05 * 197e12 * least["seconds"]
    # one pass of the same layers: a quarter of the layers' bytes and rows
    once = dict(LLM, arch=dict(LLM["arch"], total_ut_steps=1))
    single = sh.decode_step_min_seconds(once, 16, 16 * 620.0,
                                        peaks.peaks("TPU v5e"))
    assert (single["bytes"] - parts["head"] - parts["loop_end"]) * 4 == (
        least["bytes"] - parts["head"] - parts["loop_end"])
