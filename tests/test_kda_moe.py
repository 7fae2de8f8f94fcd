"""Recurrent state beside cache rows in one engine: gated delta-rule (KDA)
layers three to one with position-free latent attention and a share of
sigmoid-routed experts (`model_type` `kimi_linear`: Moonshot's Kimi Linear),
at a tiny size on the CPU: `tiny-kda-moe`, 8 layers in the published pattern
(KDA KDA KDA MLA, twice), the first dense; hidden 64; KDA 4 heads of 16, a
filter of 4; MLA 4 heads of 16 + 8 over a latent of 32; 8 experts 2 a token,
4 held; 64 positions a slot; float32. The plain reference is the benchmark's
(`benchmark/reference/kimi_linear.py`), written from the equations and
sharing only the parameter tree's names with the program. The engines here
scan their prefills in chunks of 16 positions (the model's is 64, one chunk
at this size), so that a bucket of 32 rows is two chunks."""

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
from ray_tpu.llm import LLMConfig, LLMEngine, engine as engine_module  # noqa: E402
from ray_tpu.llm.engine import ContinuousEngine, SamplingParams  # noqa: E402
from ray_tpu.llm.pipeline import make_stage_net  # noqa: E402
from ray_tpu.models.published import model_config  # noqa: E402
from ray_tpu.models import kda  # noqa: E402
from ray_tpu.models.layers import SwiGLU  # noqa: E402
from ray_tpu.models.mla import MLA  # noqa: E402
from ray_tpu.models.moe import MoE  # noqa: E402
from ray_tpu.models.transformer import (Transformer,  # noqa: E402
                                        TransformerConfig, param_specs)

MAX_SEQ, SCAN = 64, 16
ARCH = {"model_type": "kimi_linear",
        "linear_attn_config": {
            "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
            "num_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4},
        "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True,
        "num_experts": 8, "num_experts_per_token": 2,
        "num_shared_experts": 1, "moe_intermediate_size": 32,
        "intermediate_size": 128, "first_k_dense_replace": 1,
        "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
        "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": False, "hidden_act": "silu",
        "num_expert_group": 1, "topk_group": 1, "moe_layer_freq": 1,
        "num_nextn_predict_layers": 0, "rope_scaling": None}
SIZES = dict(vocab_size=96, d_model=64, n_layers=8, n_heads=4,
             max_seq=MAX_SEQ, dtype="float32", seed=0)
SHARE = dict(SIZES, arch=ARCH, experts_held=4, first_expert=0)
WHOLE = dict(SIZES, arch=ARCH)
GREEDY = dict(temperature=0.0)
WAIT_S = 120.0
#: bytes of one slot's state in one KDA layer: S, the filters' tail and the
#: last token's pending correction (alpha, k, u)
STATE_SLOT = 4 * 16 * 16 * 4 + 3 * 3 * 64 * 4 + 4 * 3 * 16 * 4

ref = manifest.load_module("benchmark/reference/kimi_linear.py")

#: name -> (prompt tokens, answer tokens): prompts shorter than the filter;
#: one that ends inside the second scan chunk of a bucket padded to 32 rows;
#: one that fills its bucket.
REGIMES = {"one_token": (1, 6), "two_tokens": (2, 6),
           "ends_inside_a_scan_chunk_of_a_padded_bucket": (21, 12),
           "fills_its_bucket": (32, 10)}


def prompt_of(n: int, seed: int = 0) -> list:
    return np.random.default_rng(seed).integers(1, 96, size=n).tolist()


def make_engine(**kw) -> ContinuousEngine:
    """An engine of the tiny model whose prefills scan in chunks of SCAN."""
    patch = pytest.MonkeyPatch()
    patch.setattr(engine_module, "model_config", lambda cfg:
                  dataclasses.replace(model_config(cfg), kda_chunk=SCAN))
    try:
        return ContinuousEngine(LLMConfig(**SHARE), **kw)
    finally:
        patch.undo()


@pytest.fixture(scope="module")
def engine():
    eng = make_engine(max_batch=2, decode_chunk=4)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def single():
    """One batch row: every request is seated where the last one left its
    state and its latent rows."""
    eng = make_engine(max_batch=1, decode_chunk=4)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def reference(engine):
    """prompt + tokens -> the reference's logits [S, V] on the engine's own
    parameters."""
    run = ref.build(SHARE).run
    return lambda seq: np.asarray(run(engine.params,
                                      np.asarray(seq, np.int32))[0])


def gaps_of(reference, prompt, toks):
    rows = reference(prompt + toks)[np.arange(len(toks)) + len(prompt) - 1]
    return rows.max(-1) - rows[np.arange(len(toks)), toks]


def alone(eng, prompt, **sampling):
    return eng.submit(prompt, SamplingParams(**GREEDY, **sampling)).tokens()


# ---------------------------------------- the chunkwise form, the recurrence
def kda_inputs(s, heads=3, dk=8, decay=1.0, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda t: t / jnp.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    shape = (2, s, heads, dk)
    q = unit(jax.random.normal(keys[0], shape)) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], shape))
    v = jax.random.normal(keys[2], shape)
    g = -decay * jnp.exp(jax.random.normal(keys[3], shape))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    s0 = jax.random.normal(keys[5], (2, heads, dk, dk))
    return q, k, v, g, beta, s0


def recurrence(q, k, v, g, beta, s0):
    """`kda.step` token by token from the state s0 with nothing pending."""
    def one(carry, at):
        o, state, pending = kda.step(*at, *carry)
        return (state, pending), o

    nothing = jnp.zeros((s0.shape[0], 3) + s0.shape[1:3]).at[:, 0].set(1.0)
    (state, pending), o = jax.lax.scan(
        one, (s0, nothing), tuple(jnp.moveaxis(t, 1, 0)
                                  for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), kda.settled(state, pending)


@pytest.mark.parametrize("s, chunk, sub", [
    (64, 64, 16), (64, 16, 4), (37, 16, 4), (37, 64, 16), (5, 16, 16),
    (48, 32, 8), (33, 8, 8), (40, 12, 16)],
    ids=lambda v: str(v))
def test_the_chunkwise_form_is_the_recurrence(s, chunk, sub):
    """Outputs and the state handed on, from a state that is not zero, for
    chunk lengths that do and do not divide the sequence, with and without
    sub-blocks."""
    x = kda_inputs(s)
    want_o, want_s = recurrence(*x)
    got_o, got_s = kda.chunk_scan(*x, chunk=chunk, sub=sub)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=2e-5)


@pytest.mark.parametrize("decay", [30.0, 300.0, 3000.0])
def test_a_strong_decay_over_a_long_chunk_overflows_nowhere(decay):
    """exp(-G_i) alone would be exp(+64 x decay): every exponent the
    chunkwise form takes is a difference that is <= 0."""
    x = kda_inputs(64, decay=decay, seed=1)
    want_o, want_s = recurrence(*x)
    got_o, got_s = kda.chunk_scan(*x, chunk=64, sub=16)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=2e-5)


def test_positions_masked_out_leave_the_state_as_it_was():
    """g = 0 and beta = 0: what a prefill does to the rows of its bucket
    past the prompt's end."""
    q, k, v, g, beta, s0 = kda_inputs(40, seed=2)
    live = (jnp.arange(40) < 27)[None, :]
    g = jnp.where(live[..., None, None], g, 0.0)
    beta = jnp.where(live[..., None], beta, 0.0)
    _, got = kda.chunk_scan(q, k, v, g, beta, s0, chunk=16, sub=4)
    _, want = recurrence(*(t[:, :27] for t in (q, k, v, g, beta)), s0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# --------------------------------------------- the layers against reference
@pytest.mark.parametrize("decode", [False, True], ids=["training", "prefill"])
def test_the_kda_layer_is_the_references(decode):
    cfg = dataclasses.replace(model_config(LLMConfig(**SHARE)),
                              kda_chunk=SCAN)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 64), jnp.float32)
    layer = kda.KDA(cfg)
    params = layer.init(jax.random.PRNGKey(4), x)["params"]
    got = layer.apply({"params": params}, x, decode=decode,
                      mutable=["cache"])[0]
    want = ref.build(SHARE).kda(x[0], params)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5)


def test_the_latent_layer_without_a_position_is_the_references():
    cfg = model_config(LLMConfig(**SHARE))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64), jnp.float32)
    pos = jnp.arange(24)[None]
    layer = MLA(cfg)
    params = layer.init(jax.random.PRNGKey(6), x, pos)["params"]
    assert "wq" in params and "wq_a" not in params  # q_lora_rank null
    got = layer.apply({"params": params}, x, pos)
    want = ref.build(SHARE).mla(x[0], params)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5)
    # no position: the same rows in another order give the same outputs
    # (a rotation would not); only the causal mask knows an order
    shifted = layer.apply({"params": params}, x, pos + 7)
    np.testing.assert_allclose(np.asarray(shifted), np.asarray(got),
                               atol=1e-6)


# ------------------------------------------------- engine against reference
def programs(engine, plen, n, prefill_len=None, spoil=None):
    """The engine's own programs, one after the other as the scheduler
    issues them (a prefill padded to its bucket, the hand-over of its
    slices into batch row 1, single-token steps through state and latent
    rows under a `kv_bound`): the logits of n tokens and the tokens."""
    prompt = prompt_of(plen)
    lb = engine._bucket(plen)
    toks = np.zeros((1, lb), np.int32)
    toks[0, :plen] = prompt
    last, slices = engine._prefill(
        engine.params, jnp.asarray(toks),
        plen if prefill_len is None else prefill_len)
    if spoil is not None:
        slices = spoil(slices)
    mirrors = (engine._toks_dev, engine._lens_dev, engine._keys,
               engine._temps_dev, engine._topks_dev, engine._topps_dev)
    first = jnp.argmax(last).astype(jnp.int32)
    # the row's last occupant left a state behind: the hand-over replaces it
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


@pytest.mark.parametrize("regime", list(REGIMES))
def test_prefill_then_decode_through_state_and_rows_give_the_references_logits(
        engine, reference, regime):
    plen, n = REGIMES[regime]
    prompt, got, served, slices = programs(engine, plen, n)
    lb = engine._bucket(plen)
    shapes = sorted({leaf.shape for leaf in jax.tree.leaves(slices)})
    # a state and a tail whole, the latent rows up to the bucket
    assert shapes == sorted([(1, 3, 192), (1, 3, 64), (1, 4, 16, 16),
                             (1, lb, 40)])
    want = reference(prompt + served)[np.arange(n) + plen - 1]
    np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("mistake", ["state_at_the_buckets_end",
                                     "tail_one_position_off"])
def test_a_wrong_hand_over_of_state_is_seen_in_the_logits(engine, reference,
                                                          mistake):
    """What the served tokens at the chip's sizes may not show, the logits
    here do: a state that went on through the padding to the bucket's last
    row, and a convolution tail taken one position late."""
    plen, n = 21, 6

    def late_tail(slices):
        return {name: ({"attn": dict(sub["attn"], conv=jnp.roll(
            sub["attn"]["conv"], -1, axis=1))} if "conv" in sub["attn"]
            else sub) for name, sub in slices.items()}

    prompt, got, served, _ = programs(
        engine, plen, n,
        prefill_len=32 if mistake == "state_at_the_buckets_end" else None,
        spoil=late_tail if mistake == "tail_one_position_off" else None)
    want = reference(prompt + served)[np.arange(n) + plen - 1]
    # (the prefill's own last logits are read at plen - 1 either way)
    assert np.abs(got[1:] - want[1:]).max() > 50 * 2e-4


@pytest.mark.parametrize("regime", list(REGIMES))
def test_served_greedy_tokens_are_the_references_best(engine, reference,
                                                      regime):
    """Through the scheduler: buckets, splices, chunks of 4, 2 and 1."""
    plen, n = REGIMES[regime]
    prompt = prompt_of(plen, seed=1)
    toks = alone(engine, prompt, max_tokens=n)
    assert len(toks) == n
    assert gaps_of(reference, prompt, toks).max() < 1e-3


def test_the_plain_llm_engine_serves_the_same_model(engine, reference):
    """`LLMEngine.generate` (an unpadded prefill, no bound) on the same
    parameters."""
    eng = LLMEngine(LLMConfig(**SHARE, params={"params": engine.params}))
    assert eng.model.cfg == model_config(LLMConfig(**SHARE))
    prompt = prompt_of(21, seed=2)
    out = eng.generate(np.asarray([prompt]), 9)[0].tolist()
    assert gaps_of(reference, prompt, out[21:]).max() < 1e-3


# ------------------------------------------------ a slot's later occupants
@pytest.mark.parametrize("later", [2, 19])
def test_a_later_occupant_starts_from_its_own_prefills_state(later, single,
                                                             reference):
    """A long request, then a short one in the row it left."""
    before = single.cache_stats()["splices"]
    first = prompt_of(41, seed=3)
    assert gaps_of(reference, first,
                   alone(single, first, max_tokens=20)).max() < 1e-3
    nxt = prompt_of(later, seed=4)
    toks = alone(single, nxt, max_tokens=14)
    assert gaps_of(reference, nxt, toks).max() < 1e-3
    assert single.cache_stats()["splices"] == before + 2


@pytest.mark.parametrize("newcomer", [3, 33])
def test_a_request_spliced_behind_a_chunk_in_flight_gets_its_own_tokens(
        newcomer, engine, reference, monkeypatch):
    """A request that stops early gives its row up while chunks that still
    step it (and read and write its state) are in flight; the next
    request's hand-over queues behind them, replaces the state whole, and
    its tokens are the reference's."""
    eng = engine
    gate, drain = threading.Event(), eng._drain

    def held_drain(ph):
        """Nothing is read while the gate is shut, but a request parked
        beside a free row is seated first. One request's slice is over the
        lane's whole parking budget here, so the stopper is prefilled only
        once the long request has left `_ready`; a scheduler that went on to
        block in the drain with the long request seated ALONE (its four
        chunks take less time to dispatch than a prefill on a loaded host)
        never seated the stopper, and the wait below ran out."""
        deadline = time.monotonic() + WAIT_S
        while not gate.is_set() and time.monotonic() < deadline:
            if eng._ready and eng._free_slot() is not None:
                return None  # go round: `_admit` seats it, nothing is read
            time.sleep(0.005)
        return drain(ph)

    monkeypatch.setattr(eng, "_drain", held_drain)
    gate.set()
    try:
        stopper = prompt_of(38, seed=5)
        base = alone(eng, stopper, max_tokens=24)
        stop = base[5]
        cut = base.index(stop) + 1
        long_p = prompt_of(9, seed=6)
        nxt = prompt_of(newcomer, seed=7)
        before = eng.cache_stats()["splices_in_flight"]
        gate.clear()
        a = eng.submit(long_p, SamplingParams(**GREEDY, max_tokens=50))
        b = eng.submit(stopper, SamplingParams(**GREEDY, max_tokens=24,
                                               stop_token=stop))
        c = eng.submit(nxt, SamplingParams(**GREEDY, max_tokens=15))
        deadline = time.monotonic() + WAIT_S
        while not (eng.num_active == 2 and len(eng._ready) == 1):
            assert time.monotonic() < deadline, "the newcomer to park"
            time.sleep(0.01)
        gate.set()
        assert b.tokens() == base[:cut] and b.finish_reason == "stop"
        assert gaps_of(reference, nxt, c.tokens()).max() < 1e-3
        assert gaps_of(reference, long_p, a.tokens()).max() < 1e-3
        assert eng.cache_stats()["splices_in_flight"] > before
    finally:
        gate.set()


# ------------------------------------------------------------ the shares
@pytest.mark.parametrize("serving", [False, True], ids=["dense", "grouped"])
def test_the_two_shares_add_up_to_the_uncut_layer_of_the_reference(serving):
    """Two chips of 4 experts each: their partial sums, with the shared
    expert (which both compute alike) counted once, are what the plain
    reference gives for the layer with all 8 experts."""
    whole = model_config(LLMConfig(**WHOLE))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 40, 64), jnp.float32)
    params = MoE(whole).init(jax.random.PRNGKey(7), x)["params"]
    want, _margin = ref.build(WHOLE).experts(x[0], params)
    total = 0.0
    for first in (0, 4):
        cfg = dataclasses.replace(
            model_config(LLMConfig(**dict(WHOLE, experts_held=4,
                                          first_expert=first))),
            moe_group_tile=4)
        cut = {k: params[k][first:first + 4]
               for k in ("w_gate", "w_up", "w_down")}
        total = total + MoE(cfg).apply({"params": {**params, **cut}}, x,
                                       serving=serving)[0]
    shared = SwiGLU(whole, d_ff=32).apply({"params": params["shared"]}, x)[0]
    np.testing.assert_allclose(np.asarray(total - shared), np.asarray(want),
                               atol=2e-5)


# ------------------------------------------------------------ model_config
def lists(kda_layers, full):
    return dict(ARCH["linear_attn_config"], kda_layers=kda_layers,
                full_attn_layers=full)


@pytest.mark.parametrize("key, value", [
    ("model_type", "kimi_vl"), ("num_expert_group", 2), ("topk_group", 2),
    ("moe_layer_freq", 2), ("num_nextn_predict_layers", 1),
    ("rope_scaling", {"type": "yarn", "factor": 4}), ("hidden_act", "gelu"),
    ("num_key_value_heads", 2),
    ("linear_attn_config", lists([1, 2, 3, 5, 6], [4, 8])),  # 7 unnamed
    ("linear_attn_config", lists([1, 2, 3, 4, 5, 6, 7], [4, 8])),  # 4 twice
    ("linear_attn_config", lists([1, 2, 3, 5, 6, 7, 7], [4, 8]))],
    ids=lambda v: str(v)[:40])
def test_model_config_refuses_what_it_does_not_build(key, value):
    with pytest.raises(ValueError):
        model_config(LLMConfig(**dict(SHARE, arch=dict(ARCH, **{key: value}))))


def test_model_config_refuses_experts_outside_the_published():
    with pytest.raises(ValueError):
        model_config(LLMConfig(**dict(SHARE, first_expert=5)))


def test_model_config_reads_every_published_key_of_the_new_arm():
    cfg = model_config(LLMConfig(**SHARE))
    assert cfg == TransformerConfig(
        vocab_size=96, d_model=64, n_layers=8, n_heads=4, max_seq=MAX_SEQ,
        dtype=jnp.dtype("float32"), n_kv_heads=4, d_ff=128,
        rope_theta=10000.0, norm_eps=1e-5, tie_embeddings=False,
        mixers=("kda", "kda", "kda", "mla") * 2, q_lora_rank=0,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, mla_rope=False, kda_heads=4, kda_head_dim=16,
        kda_conv=4, moe_experts=8, moe_top_k=2, moe_d_ff=32,
        moe_scoring="sigmoid", moe_norm_topk=True, moe_routed_scale=2.446,
        moe_score_bias=True, moe_shared_experts=1, moe_first_layer=1,
        experts_held=4, first_expert=0)
    assert [cfg.cache_kind_of(i) for i in range(8)] == [
        "state", "state", "state", "full"] * 2
    assert [cfg.is_moe_layer(i) for i in range(8)] == [False] + [True] * 7
    # layers beyond those run may be named: the published lists go on to 27
    longer = dict(ARCH, linear_attn_config=lists(
        [1, 2, 3, 5, 6, 7, 9, 10, 11], [4, 8, 12]))
    assert model_config(LLMConfig(**dict(SHARE, arch=longer))) == cfg


@pytest.mark.parametrize("name", ["phi3-mini-16l", "kimi-k2-ep32-6l",
                                  "trinity-mini-ep8-16l",
                                  "kimi-linear-ep16-16l"])
def test_every_configuration_of_the_benchmark_builds_its_mixers(name):
    """The kind of a layer's mixer is a per-layer field: Phi-3 and Trinity
    are `mha` in every layer, Kimi K2 `mla` in every layer, Kimi Linear three
    `kda` to one `mla`; nothing else about the older three changed (their
    fields: tests/test_swa_moe.py)."""
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        llm = json.load(f)["llm_config"]
    cfg = model_config(LLMConfig(**llm))
    n = cfg.n_layers
    want = {"phi3-mini-16l": ["mha"] * n, "kimi-k2-ep32-6l": ["mla"] * n,
            "trinity-mini-ep8-16l": ["mha"] * n,
            "kimi-linear-ep16-16l": ["kda", "kda", "kda", "mla"] * 4}[name]
    assert [cfg.mixer_of(i) for i in range(n)] == want
    kinds = [cfg.cache_kind_of(i) for i in range(n)]
    assert kinds.count("state") == want.count("kda")
    assert cfg.mla_rope is (name != "kimi-linear-ep16-16l")
    if name == "kimi-linear-ep16-16l":
        assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv,
                cfg.kda_chunk) == (32, 128, 4, 64)
        assert (cfg.held_experts, cfg.moe_experts, cfg.moe_top_k,
                cfg.moe_d_ff, cfg.d_ff) == (16, 256, 8, 1024, 9216)


def test_a_pipeline_stage_refuses_a_model_with_state_layers():
    """`llm/pipeline.py` keeps rows only; a wrong cache is not built."""
    mcfg = model_config(LLMConfig(**SHARE))
    with pytest.raises(NotImplementedError, match="state"):
        make_stage_net(mcfg, (0, 1, 2, 3), True, False)
    # a stage of its latent layers alone, or of a model without state
    make_stage_net(mcfg, (3,), False, False)
    make_stage_net(model_config(LLMConfig(**SIZES)), (0, 1), True, False)


def test_a_tp_mesh_refuses_a_state_leaf_and_never_takes_it_for_k_and_v():
    """A state `[slots, heads, dk, dv]` has K and V's rank; `_cache_shapes`
    goes by the layer's kind."""
    from jax.sharding import Mesh

    eng = object.__new__(ContinuousEngine)
    eng.max_batch = 2
    eng.mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    model = Transformer(model_config(LLMConfig(**SHARE)))
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    with pytest.raises(NotImplementedError, match="state leaf"):
        eng._cache_shapes(model, params)
    # the new leaves have rules of their own in the parameter specs
    specs = param_specs({"params": params})["params"]["layer_0"]["attn"]
    assert specs["A_log"] == jax.sharding.PartitionSpec("tp")
    assert specs["f_b"]["kernel"] == jax.sharding.PartitionSpec(
        None, "tp", None)
    assert specs["conv_q"] == jax.sharding.PartitionSpec(None, "tp", None)


# ----------------------------------------------- stats, spans and counters
def test_the_stats_name_the_state_beside_the_latent_rows(engine):
    alone(engine, prompt_of(20, seed=8), max_tokens=9)
    st = engine.cache_stats()
    assert st["kv_heads"] == 1 and st["cache_kind"] == "latent"
    kinds = st["cache_kinds"]
    # (a KDA layer keeps three leaves: S, the filters' tail, the correction)
    assert kinds["state"] == {"layers": 6, "leaves": 18,
                              "bytes_per_slot": 6 * STATE_SLOT,
                              "bytes": 2 * 6 * STATE_SLOT}
    assert (kinds["full"]["layers"], kinds["full"]["leaves"],
            kinds["full"]["rows"], kinds["full"]["bytes"]) == (
        2, 2, MAX_SEQ, 2 * 2 * MAX_SEQ * 40 * 4)
    assert st["state_bytes"] == kinds["state"]["bytes"]
    assert st["cache_bytes"] == sum(v["bytes"] for v in kinds.values())
    assert 0 < kinds["full"]["live_share"] <= kinds["full"]["walk_share"] <= 1
    assert st["kv_walk_share"] == kinds["full"]["walk_share"]
    assert (st["experts_held"], st["experts_published"]) == (4, 8)
    # what a parked request holds: state and tail whole, rows to the bucket
    assert engine._slice_bytes(8) == 6 * STATE_SLOT + 2 * 8 * 40 * 4
    assert engine._slice_bytes(64) - engine._slice_bytes(8) == 2 * 56 * 40 * 4
    assert engine._park_budget == st["cache_bytes"] // 4


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


def test_the_spans_carry_the_states_traffic_and_the_scans_chunks(engine,
                                                                 spans):
    tracing._ctx.set(("3" * 32, "4" * 16))
    stream = engine.submit(prompt_of(21, seed=9), SamplingParams(
        temperature=0.7, top_k=8, max_tokens=14))
    tracing._ctx.set(None)
    assert len(stream.tokens()) == 14
    deadline = time.monotonic() + WAIT_S
    while engine.num_active and time.monotonic() < deadline:
        time.sleep(0.01)
    chunks = [s["at"] for s in spans if s["n"] == "engine.dispatch_chunk"]
    assert chunks
    for at in chunks:
        # every slot's state read and written once a KDA layer a step
        assert at["state_rw_bytes"] == 2 * 2 * 6 * STATE_SLOT
        assert {"kv_rows_full", "kv_live_full", "kv_bound"} <= set(at)
        assert "kv_rows_window" not in at
    prefill = [s["at"] for s in spans if s["n"] == "engine.prefill"]
    assert prefill == [{"prompt_len": 21, "bucket": 32, "attention": "xla",
                        "scan_chunks": 2, "mixers": "kda:6,mla:2",
                        "after_seq": -1}]
    counted = [s["at"] for s in spans if s["n"] == "engine.host_sync"
               and s["at"].get("moe_steps")]
    assert sum(a["moe_steps"] for a in counted) in (13, 14)
    assert all({"moe_rows", "moe_rows_busiest"} <= set(a) for a in counted)
    # a sigmoid router's layers count what they touched and read (PR 47)
    mcfg = engine.model.cfg
    layers = mcfg.n_layers - mcfg.moe_first_layer
    for a in counted:
        assert a["moe_picks"] == a["moe_steps"] * layers * 2 * 2  # slots x k
        assert a["moe_zero_picks"] == 0
        assert a["moe_rows"] / 2 <= a["moe_touched"] <= min(
            a["moe_rows"], a["moe_fetched"])
        # the CPU's arm is the dense one: every held expert is read
        assert a["moe_fetched"] == a["moe_steps"] * layers * 4
    st = engine.cache_stats()
    assert st["moe_fetched_total"] >= sum(a["moe_fetched"] for a in counted)
    assert 0 < st["moe_touched_total"] <= st["moe_fetched_total"]
    assert st["moe_zero_picks_total"] == 0 < st["moe_picks_total"]


@pytest.mark.parametrize("degrade", ["state_bfloat16", "latent_float8",
                                     "weights_float8"])
def test_the_references_second_readings_are_of_another_model(engine,
                                                             degrade):
    """What sets the chip's tolerance: the reference with one part held
    below the configuration's precision gives other logits."""
    seq = np.asarray(prompt_of(40, seed=10), np.int32)
    exact = np.asarray(ref.build(SHARE).run(engine.params, seq)[0])
    low = np.asarray(ref.build(SHARE, degrade).run(engine.params, seq)[0])
    assert 1e-4 < np.abs(exact - low).max() < 1.0
