"""`shapes_kda_moe.py` against ISSUE 34's arithmetic for the cut
`kimi-linear-ep16-16l`, and against the parameters and the cache the program
really makes (shapes only: nothing is computed)."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, peaks, shapes_kda_moe as sh  # noqa: E402

LLM = json.load(open(os.path.join(
    ROOT, "benchmark/configs/kimi-linear-ep16-16l.json")))["llm_config"]


def test_parameter_counts_are_the_issues():
    parts = sh.param_count(LLM)
    assert sh.kda_params(LLM) == 39_514_272  # 39.51 M a KDA mixer
    assert sh.mla_params(LLM) == (2304 * 32 * 192 + 2304 * 576 + 512
                                  + 32 * 512 * 256 + 32 * 128 * 2304)
    assert round(sh.mla_params(LLM) / 1e6, 2) == 29.11
    assert sh.expert_params(LLM) == 7_077_888  # 7.08 M an expert
    assert parts["dense_ffn"] == 3 * 2304 * 9216  # 63.70 M
    assert parts["routed_experts"] == 15 * 16 * 7_077_888
    assert parts["shared_expert"] == 15 * 7_077_888
    assert parts["embedding"] == parts["head"] == 20480 * 2304
    assert round(sum(parts.values()) / 1e9, 3) == 2.562
    assert (sh.kda_layers(LLM), sh.mla_layers(LLM)) == (12, 4)
    assert sh.mixers(LLM) == ["kda", "kda", "kda", "mla"] * 4
    assert sh.expert_layers(LLM) == 15 and sh.experts_held(LLM) == 16
    # all 27 layers at 16 experts a chip: the issue's 4.30 B
    whole = dict(LLM, n_layers=27)
    assert (sh.kda_layers(whole), sh.mla_layers(whole)) == (20, 7)
    assert round(sum(sh.param_count(whole).values()) / 1e9, 2) == 4.30
    # and the model whole: 49.12 B by this count
    model = dict(whole, vocab_size=163840, experts_held=256)
    assert round(sum(sh.param_count(model).values()) / 1e9, 2) == 49.12


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
                for i in (0, 2, 3, 15)}
    assert by_layer == {i: sum(sh.layer_params(LLM, i).values())
                        for i in (0, 2, 3, 15)}
    # the cache: a state and a tail a KDA layer, a latent leaf an MLA layer
    cache = jax.eval_shape(
        lambda p: net.apply({"params": p}, jnp.zeros((64, 1), jnp.int32),
                            positions=jnp.zeros((64, 1), jnp.int32),
                            decode=True, mutable=["cache"])[1]["cache"],
        shapes)
    nbytes = lambda i: sum(s.size * s.dtype.itemsize  # noqa: E731
                           for s in jax.tree.leaves(cache[f"layer_{i}"]))
    assert nbytes(0) == 64 * (sh.state_slot_bytes(LLM)
                               + sh.pending_slot_bytes(LLM))
    assert nbytes(3) == 64 * 4096 * sh.latent_row_bytes(LLM)


def test_the_cache_is_a_state_and_latent_rows():
    assert sh.state_slot_bytes(LLM) == 32 * 128 * 128 * 4 + 3 * 12288 * 2
    assert sh.pending_slot_bytes(LLM) == 3 * 32 * 128 * 4
    assert sh.latent_row_bytes(LLM) == 1152
    got = sh.cache_bytes(LLM, 64, row_values=640)
    assert got == {"state": 12 * 64 * 2_220_032,
                   "full": 4 * 64 * 4096 * 1280}
    assert round(got["state"] / 1e9, 2) == 1.70  # S alone: 1.61
    assert round(got["full"] / 1e9, 2) == 1.34


def test_a_decode_step_is_bound_by_its_weights_and_its_state():
    # 64 slots of 1,000 visible rows: the issue's 8.4 GB, 10.2 ms
    least = sh.decode_step_min_seconds(LLM, 64, 64 * 1000.0,
                                       peaks.peaks("TPU v5e"))
    assert least["bound"] == "bandwidth"
    share = {k: v / least["bytes"] for k, v in least["parts"].items()}
    assert round(least["parts"]["state"] / 1e9, 2) == 3.33
    assert 0.36 < share["state"] < 0.40
    assert 0.38 < share["experts"] < 0.42
    assert 0.10 < share["kda_matrices"] < 0.12
    assert 10.0e-3 < least["seconds"] < 11.0e-3
    assert sh.expected_expert_rows(LLM, 64) == 15 * 32.0


def test_the_older_programs_give_the_new_readers_nothing():
    """The parent program, or another configuration: no `state_rw_bytes` on
    a chunk's span, no `linear_attn_config` in the `arch`."""
    trinity = json.load(open(os.path.join(
        ROOT, "benchmark/configs/trinity-mini-ep8-16l.json")))
    assert not sh.is_kda(trinity["llm_config"]) and sh.is_kda(LLM)
    run_ = {"spans": [], "window_wall": (0.0, 1.0), "records": [],
            "profile": None, "device": {"kind": "TPU v5e"}, "config": trinity}
    for name in ("kda_moe_step_roofline", "kda_expert_rows_per_step"):
        assert manifest.layer_reader(name)(run_) is None


def test_a_step_counts_the_held_experts_it_touched():
    """ISSUE 45: 13.8 of 16 touched a layer take 0.57 ms off the least step
    (10.57 to 10.00 ms at 64 slots of 1,000 visible rows)."""
    pk = peaks.peaks("TPU v5e")
    held = sh.decode_step_min_seconds(LLM, 64, 64 * 1000.0, pk)
    got = sh.decode_step_min_seconds(LLM, 64, 64 * 1000.0, pk,
                                     touched=15 * 13.8)
    assert (held["held"], held["touched"], got["touched"]) == (
        240, 240, 15 * 13.8)
    assert round(held["seconds"] * 1e3, 2) == 10.57
    assert round(got["seconds"] * 1e3, 2) == 10.00
    assert held["parts"]["experts"] - got["parts"]["experts"] == (
        (240 - 15 * 13.8) * 2 * sh.expert_params(LLM))
    assert {k: v for k, v in got["parts"].items() if k != "experts"} == {
        k: v for k, v in held["parts"].items() if k != "experts"}
    assert sh.decode_step_min_seconds(
        LLM, 64, 64 * 1000.0, pk, touched=240)["bytes"] == held["bytes"]
