"""A layer with its expert layer on a shortcut, and a router with identity
experts (`model_type` `longcat_flash`: Meituan's LongCat-Flash), at a tiny
size on the CPU with every ratio kept: `tiny-scmoe`, 2 layers of two latent
attentions (4 heads of 16 + 8 over a latent of 32, queries through a rank of
24), two dense SwiGLUs of 128 and one expert layer (16 routed experts of 32,
4 a token, 4 held: a share) whose softmax router has 24 outputs, the last 8
(a third) identity experts; hidden 64; 64 positions a slot; float32. The
plain reference is the benchmark's (`benchmark/reference/longcat_flash.py`),
written from the equations and sharing only the parameter tree's names with
the program. The router's matrix is the seed's times 20: at this hidden size
the seed's own gives every output nearly the same score, and a routing that
does not move from token to token would hide a wrong one."""

import dataclasses
import json
import os
import sys
import threading
import time

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
from ray_tpu.llm.engine import (ContinuousEngine, SamplingParams,  # noqa: E402
                                _moe_counters)
from ray_tpu.llm.pipeline import make_stage_net, stage_param_slice  # noqa: E402
from ray_tpu.models.published import model_config  # noqa: E402
from ray_tpu.models.moe import MoE  # noqa: E402
from ray_tpu.models.transformer import (Transformer,  # noqa: E402
                                        TransformerConfig, param_specs)

MAX_SEQ = 64
ARCH = {"model_type": "longcat_flash", "attention_method": "MLA",
        "attention_bias": False, "q_lora_rank": 24, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
        "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32,
        "n_routed_experts": 16, "zero_expert_num": 8,
        "zero_expert_type": "identity", "moe_topk": 4,
        "routed_scaling_factor": 6, "rope_theta": 10000000,
        "rms_norm_eps": 1e-5}
SIZES = dict(vocab_size=96, d_model=64, n_layers=2, n_heads=4,
             max_seq=MAX_SEQ, dtype="float32", seed=0)
SHARE = dict(SIZES, arch=ARCH, experts_held=4, first_expert=4)
WHOLE = dict(SIZES, arch=ARCH)
WAIT_S = 120.0
ATOL = 2e-4
NEW_FIELDS = {"moe_shortcut": False, "moe_zero_experts": 0,
              "mla_q_scale": 1.0, "mla_kv_scale": 1.0}

ref = manifest.load_module("benchmark/reference/longcat_flash.py")


def lively(params):
    """The seed's parameters with a router whose scores differ."""
    params = jax.tree.map(lambda x: x, params)
    for name, sub in params.items():
        if name.startswith("layer_"):
            sub["moe"]["router"] = sub["moe"]["router"] * 20.0
    return params


def seeded(llm: dict):
    net = Transformer(model_config(LLMConfig(**llm)))
    return lively(net.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])


def prompt_of(n: int, seed: int = 0) -> list:
    return np.random.default_rng(seed).integers(1, 96, size=n).tolist()


@pytest.fixture(scope="module")
def engine():
    eng = ContinuousEngine(LLMConfig(**SHARE, params=seeded(SHARE)),
                           max_batch=2, decode_chunk=4)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def single():
    """One batch row: the counters count this request's rows and no other."""
    eng = ContinuousEngine(LLMConfig(**SHARE, params=seeded(SHARE)),
                           max_batch=1, decode_chunk=4)
    yield eng
    eng.shutdown()


def reference_of(params, degrade=None):
    run = ref.build(SHARE, degrade).run
    return lambda seq: run(params, np.asarray(seq, np.int32))


# ------------------------------------------------- engine against reference
def programs(engine, plen, n):
    """The engine's own programs, one after the other as the scheduler
    issues them (a prefill padded to its bucket, the hand-over of its EIGHT
    slices, two a layer, into batch row 1, single-token steps through the
    cache under a `kv_bound`): the logits of n tokens and the tokens."""
    prompt = prompt_of(plen)
    lb = engine._bucket(plen)
    toks = np.zeros((1, lb), np.int32)
    toks[0, :plen] = prompt
    last, slices = engine._prefill(engine.params, jnp.asarray(toks), plen)
    mirrors = (engine._toks_dev, engine._lens_dev, engine._keys,
               engine._temps_dev, engine._topks_dev, engine._topps_dev)
    first = jnp.argmax(last).astype(jnp.int32)
    dirty = jax.tree.map(lambda z: z + 1.0, engine._init_cache())
    cache, mirrors = engine._place(
        dirty, slices, mirrors, first, engine._keys[0],
        np.array([1, plen, 0], np.int32), np.array([0.0, 1.0], np.float32))
    step = jax.jit(lambda cache, tok, pos, kb: engine.model.apply(
        {"params": engine.params, "cache": cache}, tok[:, None],
        positions=pos[:, None], decode=True, kv_bound=kb, mutable=["cache"]))
    got, served = [np.asarray(last)], [int(first)]
    tok, pos = mirrors[0], mirrors[1]
    for j in range(n - 1):
        logits, out = step(cache, tok, pos, jnp.int32(plen + j + 1))
        cache = out["cache"]
        got.append(np.asarray(logits[1, 0]))
        tok, pos = jnp.argmax(logits[:, 0], -1).astype(jnp.int32), pos + 1
        served.append(int(tok[1]))
    return prompt, np.stack(got), served, slices


@pytest.fixture(scope="module")
def served(engine):
    """plen -> (prompt, logits of 25 tokens, the tokens, the slices)."""
    return {plen: programs(engine, plen, 25) for plen in (21, 32)}


@pytest.mark.parametrize("plen", [21, 32],
                         ids=["padded_inside_its_bucket", "fills_its_bucket"])
def test_prefill_then_24_decode_steps_through_the_cache_give_the_references_logits(
        engine, served, plen):
    prompt, got, toks, slices = served[plen]
    # two latent leaves a layer, the rows up to the bucket
    assert sorted(jax.tree_util.keystr(path) for path, _ in
                  jax.tree_util.tree_flatten_with_path(slices)[0]) == [
        f"['layer_{i}']['attn_{j}']['latent']" for i in (0, 1) for j in (0, 1)]
    assert {leaf.shape for leaf in jax.tree.leaves(slices)} == {(1, 32, 40)}
    want = np.asarray(reference_of(engine.params)(prompt + toks)[0])
    np.testing.assert_allclose(got, want[np.arange(25) + plen - 1], atol=ATOL)


@pytest.mark.parametrize("broken", ["branch_from_u1", "joined_early",
                                    "no_identity", "no_s_q", "no_s_kv",
                                    "renormalised"])
def test_each_deliberate_break_of_the_layer_is_seen_in_the_logits(
        engine, served, broken):
    """The reference wired wrongly on purpose (the expert branch fed the
    second half's feed-forward input, joined before the second attention,
    the identity experts' part dropped, a scale left out, the weights
    renormalised) is another model than the one served."""
    prompt, got, toks, _ = served[21]
    wrong = np.asarray(reference_of(engine.params, broken)(prompt + toks)[0])
    assert np.abs(got - wrong[np.arange(25) + 20]).max() > 50 * ATOL


def test_served_greedy_tokens_are_the_references_best(engine):
    """Through the scheduler: buckets, splices, chunks of 4, 2 and 1."""
    prompt = prompt_of(21, seed=1)
    toks = engine.submit(prompt, SamplingParams(temperature=0.0,
                                                max_tokens=24)).tokens()
    rows = np.asarray(reference_of(engine.params)(prompt + toks)[0])[
        np.arange(24) + 20]
    assert (rows.max(-1) - rows[np.arange(24), toks]).max() < 1e-3


# ------------------------------------------------------------ the shares
@pytest.mark.parametrize("serving", [False, True], ids=["dense", "grouped"])
def test_the_shares_add_up_to_the_uncut_layer_of_the_reference(serving):
    """Four chips of 4 routed experts each: their partial sums, with the
    identity experts' part (which every chip computes alike for its own
    tokens) counted ONCE, are what the plain reference gives for the layer
    with all 16 experts."""
    whole = model_config(LLMConfig(**WHOLE))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 40, 64), jnp.float32)
    params = MoE(whole).init(jax.random.PRNGKey(7), x)["params"]
    params["router"] = params["router"] * 20.0
    want, _margin, picks = ref.build(WHOLE).experts(x[0], params)
    # the identity part, counted by hand from the reference's routing
    logits = np.asarray(x[0], np.float64) @ np.asarray(params["router"],
                                                       np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    picks = np.asarray(picks)
    w = 6.0 * np.take_along_axis(probs, picks, -1)
    identity = np.where(picks >= 16, w, 0.0).sum(-1)[:, None] * np.asarray(x[0])
    assert (picks >= 16).any() and (picks < 16).any()
    total = 0.0
    for first in (0, 4, 8, 12):
        cfg = dataclasses.replace(
            model_config(LLMConfig(**dict(WHOLE, experts_held=4,
                                          first_expert=first))),
            moe_group_tile=4)
        cut = {k: params[k][first:first + 4]
               for k in ("w_gate", "w_up", "w_down")}
        total = total + MoE(cfg).apply({"params": {**params, **cut}}, x,
                                       serving=serving)[0]
    np.testing.assert_allclose(np.asarray(total) - 3 * identity,
                               np.asarray(want), atol=2e-5)


def test_the_bias_moves_the_selection_and_never_the_weights():
    cfg = model_config(LLMConfig(**WHOLE))
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 12, 64), jnp.float32)
    params = MoE(cfg).init(jax.random.PRNGKey(9), x)["params"]
    params["router_bias"] = jnp.zeros(24).at[20].set(1.0)  # an identity one
    got = MoE(cfg).apply({"params": params}, x)[0]
    want, _m, picks = ref.build(WHOLE).experts(x[0], params)
    assert (np.asarray(picks) == 20).any(-1).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------------------------------------ model_config
def test_model_config_reads_every_published_key_of_the_new_arm():
    cfg = model_config(LLMConfig(**SHARE))
    assert cfg == TransformerConfig(
        vocab_size=96, d_model=64, n_layers=2, n_heads=4, max_seq=MAX_SEQ,
        dtype=jnp.dtype("float32"), n_kv_heads=4, d_ff=128,
        rope_theta=1e7, norm_eps=1e-5, tie_embeddings=False,
        mixers=("mla", "mla"), q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        mla_q_scale=(64 / 24) ** 0.5, mla_kv_scale=2 ** 0.5,
        moe_experts=16, moe_top_k=4, moe_d_ff=32, moe_scoring="softmax",
        moe_norm_topk=False, moe_routed_scale=6.0, moe_score_bias=True,
        moe_zero_experts=8, moe_shortcut=True, experts_held=4,
        first_expert=4)
    assert [cfg.cache_kind_of(i) for i in range(2)] == ["full", "full"]
    assert cfg.held_experts == 4 and _moe_counters(cfg) == 4 + 4
    # either scale off is built, as 1
    off = model_config(LLMConfig(**dict(SHARE, arch=dict(
        ARCH, mla_scale_q_lora=False, mla_scale_kv_lora=False))))
    assert off == dataclasses.replace(cfg, mla_q_scale=1.0, mla_kv_scale=1.0)


@pytest.mark.parametrize("key, value", [
    ("zero_expert_type", "copy"), ("attention_method", "MHA"),
    ("attention_bias", True), ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("norm_topk_prob", True), ("router_bias", True), ("hidden_act", "gelu"),
    ("num_key_value_heads", 2), ("model_type", "longcat")],
    ids=lambda v: str(v)[:24])
def test_model_config_refuses_what_it_does_not_build(key, value):
    with pytest.raises(ValueError):
        model_config(LLMConfig(**dict(SHARE, arch=dict(ARCH, **{key: value}))))


def test_model_config_refuses_experts_outside_the_routed():
    """`first_expert` and `experts_held` index the 16 routed experts: the
    identity experts are nobody's share."""
    with pytest.raises(ValueError):
        model_config(LLMConfig(**dict(SHARE, first_expert=13)))


def test_the_defaults_leave_the_new_fields_off():
    cfg = TransformerConfig()
    assert {k: getattr(cfg, k) for k in NEW_FIELDS} == NEW_FIELDS


@pytest.mark.parametrize("name", ["phi3-mini-16l", "kimi-k2-ep32-6l",
                                  "trinity-mini-ep8-16l",
                                  "kimi-linear-ep16-16l"])
def test_the_four_older_arms_build_their_configs_as_before(name):
    """Field for field: nothing of the fifth arm reaches them (their own
    fields are pinned by tests/test_mla_moe.py, test_swa_moe.py and
    test_kda_moe.py, whose equalities hold the new fields to their
    defaults too)."""
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        llm = json.load(f)["llm_config"]
    cfg = model_config(LLMConfig(**llm))
    assert {k: getattr(cfg, k) for k in NEW_FIELDS} == NEW_FIELDS
    assert _moe_counters(cfg) == cfg.held_experts + 4 * bool(cfg.held_experts)
    fields = {f.name for f in dataclasses.fields(cfg)}
    assert fields - set(NEW_FIELDS) == {
        "vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_ff",
        "max_seq", "rope_theta", "dtype", "param_dtype", "norm_eps",
        "head_size", "sliding_window", "window_layers", "rope_window_only",
        "qk_norm", "attn_gate", "sandwich_norm", "emb_scale",
        "tie_embeddings", "mixers", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_yarn",
        "mla_rope", "kda_heads", "kda_head_dim", "kda_conv", "kda_chunk",
        "moe_experts", "moe_top_k", "moe_d_ff", "moe_scoring",
        "moe_norm_topk", "moe_routed_scale", "moe_score_bias",
        "moe_shared_experts", "moe_first_layer", "experts_held",
        "first_expert", "moe_group_tile", "cache_row",
        # PR 49's, for the `evabyte` arm alone (tests/test_eva.py)
        "eva_window", "eva_chunk", "eva_pool_std", "norm_unit_offset",
        "residual_f32", "pred_heads",
        # PR 53's, for the `ouro` arm alone (tests/test_ouro.py)
        "ut_steps",
        # PR 58's, for the `sdar_moe` arm alone (tests/test_blockdiff.py)
        "block_length", "denoising"}
    assert cfg.ut_steps == 1
    assert (cfg.block_length, cfg.denoising) == (0, None)
    assert (cfg.eva_window, cfg.eva_chunk, cfg.norm_unit_offset,
            cfg.residual_f32, cfg.pred_heads) == (0, 0, False, False, 1)


OLDER_EXPERT_ARMS = {
    "kimi_k2": {"model_type": "kimi_k2", "intermediate_size": 96,
                "q_lora_rank": 24, "kv_lora_rank": 16,
                "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                "v_head_dim": 16, "moe_intermediate_size": 32,
                "n_routed_experts": 8, "n_shared_experts": 1,
                "num_experts_per_tok": 2, "first_k_dense_replace": 1,
                "norm_topk_prob": True, "routed_scaling_factor": 2.5,
                "scoring_func": "sigmoid", "rms_norm_eps": 1e-5,
                "rope_theta": 50000, "rope_scaling": None,
                "tie_word_embeddings": False},
    "afmoe": {"model_type": "afmoe", "head_dim": 16,
              "num_key_value_heads": 2, "intermediate_size": 96,
              "layer_types": ["sliding_attention", "full_attention"],
              "sliding_window": 16, "rope_theta": 10000,
              "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
              "num_experts": 8, "num_experts_per_tok": 2,
              "moe_intermediate_size": 32, "score_func": "sigmoid",
              "route_norm": True, "route_scale": 2.5,
              "num_shared_experts": 1, "num_dense_layers": 1},
    "kimi_linear": {"model_type": "kimi_linear",
                    "linear_attn_config": {
                        "kda_layers": [1], "full_attn_layers": [2],
                        "num_heads": 4, "head_dim": 16,
                        "short_conv_kernel_size": 4},
                    "kv_lora_rank": 16, "q_lora_rank": None,
                    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                    "v_head_dim": 16, "mla_use_nope": True, "num_experts": 8,
                    "num_experts_per_token": 2, "num_shared_experts": 1,
                    "moe_intermediate_size": 32, "intermediate_size": 96,
                    "first_k_dense_replace": 1,
                    "moe_router_activation_func": "sigmoid",
                    "moe_renormalize": True, "routed_scaling_factor": 2.5,
                    "rms_norm_eps": 1e-5, "rope_scaling": None,
                    "tie_word_embeddings": False}}


@pytest.mark.parametrize("arm", list(OLDER_EXPERT_ARMS))
def test_an_older_arms_expert_layer_sows_what_it_sowed_and_nothing_new(arm):
    """`expert_rows` and, since PR 47, the four of `picks` that every
    expert layer sows (their rooflines count the experts a step touched by
    them): no selection on an identity expert, `moe_touched` from the rows,
    and on the CPU every held expert read."""
    cfg = model_config(LLMConfig(**dict(SIZES, arch=OLDER_EXPERT_ARMS[arm],
                                        experts_held=4)))
    net = Transformer(cfg)
    toks = jnp.asarray([prompt_of(8)])
    params = net.init(jax.random.PRNGKey(0), toks)["params"]
    _, out = net.apply({"params": params}, toks, mutable=["stats"])
    sown = {jax.tree_util.keystr(path[-1:]): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(out["stats"])[0]}
    assert sown == {"['expert_rows']": (4,), "['picks']": (4,)}
    moe = out["stats"]["layer_1"]["moe"]
    picks, zero, touched, fetched = np.asarray(moe["picks"])
    assert (picks, zero, fetched) == (8 * 2, 0, 4)
    assert touched == (np.asarray(moe["expert_rows"]) > 0).sum() > 0
    assert "router_bias" in params["layer_1"]["moe"]
    # the bias of a sigmoid router keeps its scale: the seeds' values stand
    bias = np.asarray(params["layer_1"]["moe"]["router_bias"])
    assert 0.003 < np.abs(bias).mean() < 0.03


def test_the_new_arms_expert_layer_sows_its_four_counters_too():
    """The fourth, the held experts whose weights the call's arm read, is
    every held one here: the CPU keeps the dense arm (PR 43)."""
    net = Transformer(model_config(LLMConfig(**SHARE)))
    toks = jnp.asarray([prompt_of(8)])
    _, out = net.apply({"params": seeded(SHARE)}, toks, mutable=["stats"])
    moe = out["stats"]["layer_0"]["moe"]
    assert {k: v.shape for k, v in moe.items()} == {"expert_rows": (4,),
                                                    "picks": (4,)}
    picks, zero, touched, fetched = np.asarray(moe["picks"])
    assert picks == 8 * 4 and 0 < zero < picks
    assert touched == (np.asarray(moe["expert_rows"]) > 0).sum()
    assert fetched == 4


# ------------------------------------------------------------- param_specs
def test_param_specs_reach_every_new_leaf_by_a_rule_of_its_own():
    """Norms and the router's bias aside, no leaf of the new block falls to
    the replicated default."""
    net = Transformer(model_config(LLMConfig(**SHARE)))
    params = jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    specs = param_specs({"params": params})["params"]["layer_0"]
    P = jax.sharding.PartitionSpec
    flat = {jax.tree_util.keystr(path): spec for path, spec in
            jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda s: isinstance(s, P))[0]}
    replicated = {k for k, spec in flat.items() if spec == P()}
    assert replicated == {
        "['attn_0']['kv_norm']['scale']", "['attn_0']['q_norm']['scale']",
        "['attn_1']['kv_norm']['scale']", "['attn_1']['q_norm']['scale']",
        "['attn_norm_0']['scale']", "['attn_norm_1']['scale']",
        "['mlp_norm_0']['scale']", "['mlp_norm_1']['scale']",
        "['moe']['router_bias']"}
    assert len(flat) == 2 * 8 + 2 * 3 + 4 + 5
    for j in (0, 1):
        assert specs[f"attn_{j}"]["wq_b"]["kernel"] == P(None, "tp", None)
        assert specs[f"attn_{j}"]["wk_b"] == P("tp", None, None)
        assert specs[f"mlp_{j}"]["w_down"]["kernel"] == P("tp", "fsdp")
    assert specs["moe"]["w_gate"] == P("ep", "fsdp", "tp")
    assert specs["moe"]["router"] == P("fsdp", None)


# ---------------------------------------------------------- pipeline stage
def test_a_one_stage_net_of_the_new_block_is_the_full_transformer():
    """`llm/pipeline.py` maps over whatever leaves a stage's layers keep, so
    a layer with two latent leaves needs nothing of its own there."""
    mcfg = model_config(LLMConfig(**SHARE))
    params = seeded(SHARE)
    toks = jnp.asarray([prompt_of(12, seed=3)])
    pos = jnp.arange(12)[None]
    want = Transformer(mcfg).apply({"params": params}, toks)
    net = make_stage_net(mcfg, (0, 1), True, True)
    got, out = net.apply(
        {"params": stage_param_slice(params, (0, 1), True, True)}, toks, pos,
        mutable=["cache"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert len(jax.tree.leaves(out["cache"])) == 4


# ----------------------------------------------- stats, spans and counters
def test_the_stats_count_leaves_beside_layers_and_name_the_identity_experts(
        engine):
    engine.submit(prompt_of(20, seed=8), SamplingParams(
        temperature=0.0, max_tokens=9)).tokens()
    st = engine.cache_stats()
    assert st["kv_heads"] == 1 and st["cache_kind"] == "latent"
    full = st["cache_kinds"]["full"]
    assert (full["layers"], full["leaves"], full["rows"], full["bytes"]) == (
        2, 4, MAX_SEQ, 4 * 2 * MAX_SEQ * 40 * 4)
    assert st["cache_bytes"] == full["bytes"]
    assert 0 < full["live_share"] <= full["walk_share"] <= 1
    assert (st["experts_held"], st["experts_published"], st["first_expert"],
            st["zero_experts"], st["router_outputs"]) == (4, 16, 4, 8, 24)
    assert 0 < st["moe_zero_picks_total"] < st["moe_picks_total"]
    assert st["moe_picks_total"] % (2 * 4 * 2) == 0  # rows x picks x layers
    # what a parked request holds: four leaves' rows up to its bucket
    assert engine._slice_bytes(32) == 4 * 32 * 40 * 4
    assert engine._decode_form == "xla" and st["decode_steps_kernel"] == 0


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


def test_the_counters_are_the_references_routing_counted_by_hand(single,
                                                                 spans):
    """`moe_picks`, `moe_zero_picks`, `moe_touched` and `moe_rows` of a
    request alone in a batch of one row, against NumPy counts over the
    reference's own selections at the positions its decode steps ran;
    `moe_fetched` every held expert of every layer and step (the CPU's
    arm is the dense one)."""
    n, prompt = 14, prompt_of(21, seed=9)
    before = single.cache_stats()
    tracing._ctx.set(("3" * 32, "4" * 16))
    stream = single.submit(prompt, SamplingParams(temperature=0.0,
                                                  max_tokens=n))
    tracing._ctx.set(None)
    toks = stream.tokens()
    deadline = time.monotonic() + WAIT_S
    while single.num_active and time.monotonic() < deadline:
        time.sleep(0.01)
    counted = [s["at"] for s in spans if s["n"] == "engine.host_sync"
               and s["at"].get("moe_steps")]
    steps = sum(a["moe_steps"] for a in counted)
    assert steps in (n - 1, n)  # (the last step's token is nobody's)
    picks = np.stack([np.asarray(p) for p in
                      reference_of(single.params)(prompt + toks)[2]])
    at = picks[:, 21:21 + steps]  # [layers, steps, k]: step j reads token j
    held = (at >= 4) & (at < 8)
    got = {k: sum(a[k] for a in counted) for k in (
        "moe_picks", "moe_zero_picks", "moe_touched", "moe_rows",
        "moe_fetched")}
    assert got == {"moe_picks": at.size, "moe_zero_picks": int((at >= 16).sum()),
                   "moe_touched": int(held.sum()),  # one row: a pick a touch
                   "moe_rows": int(held.sum()),
                   "moe_fetched": 4 * at.shape[0] * steps}
    assert 0 < got["moe_zero_picks"] < got["moe_picks"]
    after = single.cache_stats()
    assert after["moe_picks_total"] - before["moe_picks_total"] == at.size
    assert (after["moe_zero_picks_total"] - before["moe_zero_picks_total"]
            == got["moe_zero_picks"])
    for name in ("moe_touched", "moe_fetched"):
        assert after[f"{name}_total"] - before[f"{name}_total"] == got[name]
