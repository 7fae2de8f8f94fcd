"""Latent attention and the expert layer (models/mla.py, models/moe.py) and
what the serving engine does with them, at a tiny size on the CPU: the
DeepSeek-V3 / Kimi K2 block at hidden 64, 4 heads, ranks 24/16, 16 experts
top-4 of which a share holds 4. The plain reference is the benchmark's
(`benchmark/reference/kimi_k2.py`), written from the equations and sharing
only the parameter tree's names with the program."""

import dataclasses
import math
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from ray_tpu._private import tracing  # noqa: E402
from ray_tpu.llm import LLMConfig  # noqa: E402
from ray_tpu.llm.engine import ContinuousEngine, SamplingParams  # noqa: E402
from ray_tpu.models import layers  # noqa: E402
from ray_tpu.models.published import model_config  # noqa: E402
from ray_tpu.models.mla import softmax_scale  # noqa: E402
from ray_tpu.models.moe import MoE  # noqa: E402
from ray_tpu.models.transformer import Transformer  # noqa: E402

YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
ARCH = {"model_type": "kimi_k2", "hidden_act": "silu",
        "intermediate_size": 160, "q_lora_rank": 24, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "moe_intermediate_size": 32, "n_routed_experts": 16,
        "n_shared_experts": 1, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 2.827, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "rms_norm_eps": 1e-5, "rope_theta": 50000, "rope_scaling": YARN,
        "tie_word_embeddings": False, "num_key_value_heads": 4}
SIZES = dict(vocab_size=300, d_model=64, n_layers=3, n_heads=4, max_seq=128,
             dtype="float32", seed=0)
SHARE = dict(SIZES, arch=ARCH, experts_held=4, first_expert=4)
WHOLE = dict(SIZES, arch=ARCH)
PHI = dict(SIZES)

kimi_ref = manifest.load_module("benchmark/reference/kimi_k2.py")
phi3_ref = manifest.load_module("benchmark/reference/phi3.py")


def small_tiles(llm: dict, tile: int = 4):
    """The model of `llm` with the grouped path's tile shrunk, so that a
    prompt of a few dozen rows takes it."""
    return dataclasses.replace(model_config(LLMConfig(**llm)),
                               moe_group_tile=tile)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(0)
    return [rng.randint(0, 300, size=n).tolist() for n in (5, 19, 40)]


# ------------------------------------------------- engine against reference
@pytest.mark.parametrize("llm, ref, tile", [
    (SHARE, kimi_ref, 128), (SHARE, kimi_ref, 4), (WHOLE, kimi_ref, 4),
    (PHI, phi3_ref, 128)],
    ids=["kimi-share-dense-prefill", "kimi-share-grouped-prefill",
         "kimi-all-held", "phi3"])
def test_engine_prefill_and_cached_decode_agree_with_the_plain_reference(
        llm, ref, tile, prompts, monkeypatch):
    """Greedy tokens served through prefill buckets, splices and cached
    decode chunks are the reference's best tokens, to float32 rounding."""
    if llm.get("arch"):
        from ray_tpu.llm import engine as eng_mod
        real = eng_mod.model_config
        monkeypatch.setattr(eng_mod, "model_config", lambda cfg: (
            dataclasses.replace(real(cfg), moe_group_tile=tile)))
    eng = ContinuousEngine(LLMConfig(**llm), max_batch=2, decode_chunk=4)
    try:
        outs = eng.generate(prompts, SamplingParams(temperature=0.0,
                                                    max_tokens=9))
        built = ref.build(llm)
        logits = getattr(built, "run", built)
        for prompt, toks in zip(prompts, outs):
            out = logits(eng.params, np.asarray(prompt + toks, np.int32))
            out = np.asarray(out[0] if isinstance(out, tuple) else out)
            rows = out[np.arange(len(toks)) + len(prompt) - 1]
            gaps = rows.max(-1) - rows[np.arange(len(toks)), toks]
            assert gaps.max() < 1e-3, gaps
        if llm.get("arch"):
            st = eng.cache_stats()
            assert st["cache_kind"] == "latent"
            assert st["cache_boundary_copies"] == 0
            assert st["experts_published"] == 16
            assert st["experts_held"] == (llm.get("experts_held") or 16)
            assert st["moe_rows_total"] == eng.moe_rows_total > 0
            assert st["cache_layout"].startswith("float32[2, 128, 24] ")
            assert st["cache_bytes"] == 3 * 2 * 128 * 24 * 4
        else:
            assert eng.cache_stats()["cache_kind"] == "kv"
            assert "experts_held" not in eng.cache_stats()
    finally:
        eng.shutdown()


def test_the_plain_llm_engine_derives_its_model_in_the_same_place():
    from ray_tpu.llm import LLMEngine

    eng = LLMEngine(LLMConfig(**SHARE))
    assert eng.model.cfg == model_config(LLMConfig(**SHARE))
    out = eng.generate(np.asarray([[5, 6, 7, 8]]), 3)
    assert out.shape == (1, 7)


@pytest.mark.parametrize("key, value", [
    ("model_type", "llama"), ("n_group", 8), ("topk_method", "greedy"),
    ("hidden_act", "gelu")])
def test_model_config_refuses_what_it_does_not_build(key, value):
    with pytest.raises(ValueError):
        model_config(LLMConfig(**dict(SHARE, arch=dict(ARCH, **{key: value}))))


def test_model_config_refuses_experts_outside_the_published():
    with pytest.raises(ValueError):
        model_config(LLMConfig(**dict(SHARE, first_expert=13)))
    with pytest.raises(ValueError):
        model_config(LLMConfig(**dict(PHI, experts_held=2)))


# ------------------------------------------------ latent against expanded
@pytest.mark.parametrize("row", [0, 32], ids=["row-as-is", "row-widened"])
def test_latent_space_decode_equals_the_expanded_attention(row):
    """A prefill and then single-token steps through the latent cache give
    the logits of one uncached forward pass over the whole sequence."""
    cfg = dataclasses.replace(model_config(LLMConfig(**SHARE)), cache_row=row)
    model = Transformer(cfg)
    tokens = jnp.asarray(np.random.RandomState(3).randint(0, 300, (2, 24)))
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    want = model.apply({"params": params}, tokens)
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    got, state = model.apply({"params": params}, tokens[:, :16],
                             positions=pos[:, :16], decode=True,
                             mutable=["cache"])
    leaf = state["cache"]["layer_0"]["attn"]["latent"]
    assert leaf.shape == (2, 128, max(row, 24))
    steps = [got]
    for t in range(16, 24):
        out, state = model.apply({"params": params, **state},
                                 tokens[:, t:t + 1], positions=pos[:, t:t + 1],
                                 decode=True, mutable=["cache"])
        steps.append(out)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(steps, 1)),
                               np.asarray(want), atol=2e-4)


# --------------------------------------------------------- the expert layer
def expert_layer(llm, **kw):
    cfg = dataclasses.replace(model_config(LLMConfig(**llm)), **kw)
    return MoE(cfg), cfg


@pytest.fixture(scope="module")
def whole_layer():
    """An uncut expert layer's parameters (all 16 experts) and some rows."""
    layer, _cfg = expert_layer(WHOLE)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 64), jnp.float32)
    params = layer.init(jax.random.PRNGKey(7), x)["params"]
    return params, x


def share_of(params, first, held):
    cut = {k: params[k][first:first + held]
           for k in ("w_gate", "w_up", "w_down")}
    return {**params, **cut}


@pytest.mark.parametrize("serving", [False, True], ids=["dense", "grouped"])
def test_the_shares_add_up_to_the_uncut_layer_of_the_reference(
        whole_layer, serving):
    """Four chips of 4 experts each: their partial sums, with the shared
    expert (which every chip computes alike) counted once, are what the
    plain reference gives for the layer with all 16 experts."""
    params, x = whole_layer
    ref = kimi_ref.build(WHOLE)
    want, _margin = ref.experts(x[0], jax.tree.map(jnp.asarray, params))
    shared_layer, _ = expert_layer(WHOLE)
    total = 0.0
    for first in (0, 4, 8, 12):
        layer, _cfg = expert_layer(dict(WHOLE, experts_held=4,
                                        first_expert=first),
                                   moe_group_tile=4)
        total = total + layer.apply({"params": share_of(params, first, 4)},
                                    x, serving=serving)[0]
    # the shared expert came with every share: take it off three times
    from ray_tpu.models.layers import SwiGLU
    shared = SwiGLU(shared_layer.cfg, d_ff=32).apply(
        {"params": params["shared"]}, x)[0]
    np.testing.assert_allclose(np.asarray(total - 3 * shared),
                               np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("serving", [False, True], ids=["dense", "grouped"])
@pytest.mark.parametrize("case", ["every-row-held", "no-row-held",
                                  "one-expert-takes-all"])
def test_no_row_is_dropped_whatever_the_routing(whole_layer, serving, case):
    """Static shapes and no capacity: a share's sum is the reference's when
    every selection falls on held experts, when none does (the shared
    expert alone is left), and when one held expert is selected by every
    row (48 rows in tiles of 4)."""
    params, x = whole_layer
    bias = np.zeros(16, np.float32)
    if case == "every-row-held":
        first, held = 0, 16
    elif case == "no-row-held":
        first, held = 0, 4
        bias[8:] = 10.0  # every row selects four of experts 8..15
    else:
        first, held = 4, 4
        bias[5] = 10.0  # every row selects expert 5, held here
    params = {**params, "router_bias": jnp.asarray(bias)}
    llm = dict(WHOLE, experts_held=held, first_expert=first)
    layer, _cfg = expert_layer(llm, moe_group_tile=4)
    got, stats = layer.apply({"params": share_of(params, first, held)}, x,
                             serving=serving, mutable=["stats"])
    want, _margin = kimi_ref.build(llm).experts(
        x[0], share_of(params, first, held))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5)
    rows = np.asarray(stats["stats"]["expert_rows"])
    if case == "every-row-held":
        assert rows.sum() == 48 * 4
    elif case == "no-row-held":
        assert rows.sum() == 0
        from ray_tpu.models.layers import SwiGLU
        shared = SwiGLU(layer.cfg, d_ff=32).apply(
            {"params": params["shared"]}, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(shared),
                                   atol=1e-6)
    else:
        assert rows[1] == 48 and rows.max() == 48


def test_the_grouped_path_runs_as_many_tiles_as_the_rows_routed_here_fill(
        whole_layer, monkeypatch):
    """The prefill's expert work follows the rows routed to held experts:
    the loop's trip count is sum over held experts of ceil(rows / tile),
    not held experts x rows / tile."""
    params, x = whole_layer
    layer, _cfg = expert_layer(dict(WHOLE, experts_held=4, first_expert=8),
                               moe_group_tile=4)
    trips = []
    real = jax.lax.fori_loop

    def counting(lower, upper, body, init):
        trips.append(int(upper))
        return real(lower, upper, body, init)

    monkeypatch.setattr(jax.lax, "fori_loop", counting)
    with jax.disable_jit():
        _y, stats = layer.apply({"params": share_of(params, 8, 4)}, x,
                                serving=True, mutable=["stats"])
    rows = np.asarray(stats["stats"]["expert_rows"])
    assert trips == [int(np.ceil(rows / 4).sum())]
    assert 0 < rows.sum() < 48 * 4  # a share: some selections lie elsewhere
    assert trips[0] <= rows.sum() // 4 + 4 < 4 * 48 // 4


def test_the_old_top2_softmax_mixture_is_the_same_layer():
    """`TransformerConfig(moe_experts=4)`, as the training dry-run and
    tests/test_parallel.py use it: softmax scores, the best two renormalised,
    every expert held, no shared expert, no bias; and it differentiates."""
    from ray_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=4,
                            n_kv_heads=4, d_ff=48, max_seq=16,
                            dtype=jnp.float32, moe_experts=4)
    layer = MoE(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 32))
    params = layer.init(jax.random.PRNGKey(3), x)["params"]
    assert set(params) == {"router", "w_gate", "w_up", "w_down"}
    probs = jax.nn.softmax(x @ params["router"], -1)
    top, idx = jax.lax.top_k(probs, 2)
    gates = top / top.sum(-1, keepdims=True)
    want = 0.0
    for j in range(2):
        e = idx[..., j]
        h = (jax.nn.silu(jnp.einsum("bsd,bsdf->bsf", x, params["w_gate"][e]))
             * jnp.einsum("bsd,bsdf->bsf", x, params["w_up"][e]))
        want = want + gates[..., j:j + 1] * jnp.einsum(
            "bsf,bsfd->bsd", h, params["w_down"][e])
    np.testing.assert_allclose(np.asarray(layer.apply({"params": params}, x)),
                               np.asarray(want), atol=1e-5)
    grads = jax.grad(lambda p: layer.apply({"params": p}, x).sum())(params)
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grads))


# -------------------------------------------------------------------- YaRN
def hand_inv_freq(i: int) -> float:
    """dim 64, theta 50000, factor 64, original 4096, beta 32 / 1: the
    correction dims are floor(8.91) = 8 and ceil(19.16) = 20."""
    extra = 50000.0 ** (-2 * i / 64)
    ramp = min(max((i - 8) / 12, 0.0), 1.0)
    return extra / 64 * ramp + extra * (1 - ramp)


@pytest.mark.parametrize("i", [0, 7, 8, 11, 14, 19, 20, 31])
def test_yarn_inverse_frequencies_at_the_published_settings(i):
    assert math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                      / (2 * math.log(50000))) == 8
    assert math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(50000))) == 20
    yarn = layers.YarnScaling.from_config(YARN)
    got = np.asarray(layers.rope_inv_freq(64, 50000.0, yarn))
    assert got.shape == (32,)
    np.testing.assert_allclose(got[i], hand_inv_freq(i), rtol=1e-5)
    ref = np.asarray(kimi_ref.yarn_inv_freq(64, 50000.0, YARN))
    np.testing.assert_allclose(ref[i], hand_inv_freq(i), rtol=1e-5)


def test_yarn_scales_the_softmax_and_not_cos_and_sin():
    yarn = layers.YarnScaling.from_config(YARN)
    assert layers.rope_cos_sin_scale(yarn) == 1.0
    m = 0.1 * math.log(64) + 1
    assert abs(m - 1.4159) < 1e-4
    cfg = dataclasses.replace(model_config(LLMConfig(**SHARE)),
                              qk_nope_head_dim=128, qk_rope_head_dim=64)
    assert abs(softmax_scale(cfg) - 192 ** -0.5 * m * m) < 1e-9


def test_interleaved_rope_gives_the_dot_products_of_deepseeks_permuted_form():
    """The served rotation turns pairs (2i, 2i+1) in place; the reference
    permutes to [evens | odds] and rotates halves. Scores agree."""
    inv = layers.rope_inv_freq(8, 50000.0, layers.YarnScaling.from_config(YARN))
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 6, 2, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, 6, 1, 8), jnp.float32)
    pos = jnp.arange(6)[None]
    a = jnp.einsum("bqhd,btd->bhqt", layers.rope_interleaved(q, pos, inv),
                   layers.rope_interleaved(k, pos, inv)[:, :, 0])

    def permuted(x):
        x = x.reshape(*x.shape[:-1], 4, 2)
        x = jnp.concatenate([x[..., 0], x[..., 1]], -1)
        ang = pos[0][:, None] * inv
        cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
        sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
        rot = jnp.concatenate([-x[..., 4:], x[..., :4]], -1)
        return x * cos + rot * sin

    b = jnp.einsum("bqhd,btd->bhqt", permuted(q), permuted(k)[:, :, 0])
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------- start-up
@pytest.mark.parametrize("llm", [SHARE, PHI], ids=["kimi", "phi3"])
def test_parameters_are_made_in_the_serving_dtype_leaf_by_leaf(llm):
    """bf16 serving: one program makes each leaf and casts it, so no float32
    copy of the tree ever exists; and the values are those of an eager
    float32 init followed by the cast, bit for bit."""
    cfg = LLMConfig(**dict(llm, dtype="bfloat16"))
    eng = ContinuousEngine(cfg, max_batch=2, decode_chunk=2)
    try:
        leaves = jax.tree.leaves(eng.params)
        assert {leaf.dtype for leaf in leaves} == {jnp.dtype("bfloat16")}
        eager = Transformer(model_config(cfg)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        for got, want in zip(leaves, jax.tree.leaves(eager)):
            assert want.dtype == jnp.float32
            np.testing.assert_array_equal(
                np.asarray(got.astype(jnp.float32)),
                np.asarray(want.astype(jnp.bfloat16).astype(jnp.float32)))
        # the program that made them returns bf16 leaves only
        out = jax.eval_shape(eng._make_params, jax.random.PRNGKey(0))
        assert {s.dtype for s in jax.tree.leaves(out)} == {
            jnp.dtype("bfloat16")}
        assert len(jax.tree.leaves(out)) == len(leaves)
    finally:
        eng.shutdown()


# ------------------------------------------------------------------ tracing
@pytest.fixture
def spans(monkeypatch):
    caught, lock = [], threading.Lock()

    def record_span(trace_id, span_id, parent, name, kind, start, end,
                    attrs=None):
        with lock:
            caught.append({"n": name, "at": attrs or {}})

    monkeypatch.setattr(tracing, "_ON", True)
    monkeypatch.setattr(tracing, "record_span", record_span)
    yield caught
    tracing._ctx.set(None)


def test_the_expert_rows_ride_with_the_tokens_into_the_host_sync_span(spans):
    eng = ContinuousEngine(LLMConfig(**SHARE), max_batch=2, decode_chunk=4)
    try:
        tracing._ctx.set(("1" * 32, "2" * 16))
        stream = eng.submit([1, 2, 3, 4], SamplingParams(
            temperature=0.7, top_k=8, max_tokens=12))
        tracing._ctx.set(None)
        assert len(stream.tokens()) == 12
        syncs = [s["at"] for s in spans if s["n"] == "engine.host_sync"]
        counted = [a for a in syncs if a.get("moe_steps")]
        assert counted and all(
            {"moe_rows", "moe_rows_busiest", "moe_steps", "moe_picks",
             "moe_zero_picks", "moe_touched", "moe_fetched"} <= set(a)
            for a in counted)
        # 12 tokens: the first from the prefill, then chunks of 4 steps
        assert sum(a["moe_steps"] for a in counted) == 12
        assert sum(a["moe_rows"] for a in counted) == eng.moe_rows_total
        held = eng.cache_stats()["experts_held"]
        for a in counted:
            # 2 slots x 4 selections x 2 expert layers a step, at most
            assert (0 <= a["moe_rows_busiest"] <= a["moe_rows"]
                    <= a["moe_steps"] * 16 == a["moe_picks"])
            # a sigmoid router's layers count what they touched (PR 47):
            # at most a row's worth, at least the rows over the batch; the
            # CPU's arm is the dense one, which reads every held expert
            assert a["moe_zero_picks"] == 0
            assert a["moe_rows"] / 2 <= a["moe_touched"] <= min(
                a["moe_rows"], a["moe_fetched"])
            assert a["moe_fetched"] == a["moe_steps"] * 2 * held
        st = eng.cache_stats()
        assert st["moe_touched_total"] == sum(
            a["moe_touched"] for a in counted) > 0
        assert st["moe_fetched_total"] == sum(
            a["moe_fetched"] for a in counted)
        assert (st["moe_picks_total"], st["moe_zero_picks_total"]) == (
            12 * 16, 0)
        assert "zero_experts" not in st  # (no identity experts here)
    finally:
        eng.shutdown()


def test_a_model_without_expert_layers_carries_no_extra_columns():
    eng = ContinuousEngine(LLMConfig(**PHI), max_batch=2, decode_chunk=4)
    try:
        assert eng._moe_cols == 0 and eng._moe_held == 0
        eng._cache = eng._init_cache()
        out = jax.eval_shape(
            eng._chunk.jitted, eng.params, eng._cache, eng._toks_dev, eng._lens_dev,
            eng._keys, eng._temps_dev, eng._topks_dev, eng._topps_dev, 4,
            False)
        assert out[2].shape == (2, 4)
    finally:
        eng.shutdown()


def test_more_held_experts_than_slots_take_more_columns():
    eng = ContinuousEngine(LLMConfig(**WHOLE), max_batch=3, decode_chunk=2)
    try:
        # 16 rows' counts and the layers' four (PR 47) on 3 slots: 7 columns
        assert (eng._moe_held, eng._moe_cols) == (16, 7)
        toks = eng.generate([[1, 2, 3], [4, 5]], SamplingParams(
            temperature=0.0, max_tokens=5))
        assert [len(t) for t in toks] == [5, 5]
        # every selection is held: 3 slots x 4 selections x 2 layers a step
        assert eng.moe_rows_total % 24 == 0 and eng.moe_rows_total > 0
    finally:
        eng.shutdown()
