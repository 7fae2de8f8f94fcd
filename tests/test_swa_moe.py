"""Window and full attention layers with two kinds of cache leaf in one
engine, grouped-query gated attention and a share of sigmoid-routed experts
(`model_type` `afmoe`: Arcee's Trinity family), at a tiny size on the CPU:
`tiny-swa-moe`, 8 layers = 2 dense + 6 expert in the published pattern
(three window layers, then a full one), window 16, 64 positions a slot, 4
heads on 2 key/value heads of 16, 8 experts 2 a token, 4 held. The plain
reference is the benchmark's (`benchmark/reference/trinity.py`), written from
the equations and sharing only the parameter tree's names with the program."""

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
from ray_tpu.llm import LLMConfig, LLMEngine  # noqa: E402
from ray_tpu.llm.engine import ContinuousEngine, SamplingParams  # noqa: E402
from ray_tpu.llm.pipeline import make_stage_net  # noqa: E402
from ray_tpu.models.published import model_config  # noqa: E402
from ray_tpu.models.layers import SwiGLU  # noqa: E402
from ray_tpu.models.moe import MoE  # noqa: E402
from ray_tpu.models.transformer import (TransformerConfig,  # noqa: E402
                                        YarnScaling, prefill_attention)
from ray_tpu.ops.decode_attention import kv_prefix_rows  # noqa: E402

WINDOW, MAX_SEQ = 16, 64
ARCH = {"model_type": "afmoe", "num_key_value_heads": 2, "head_dim": 16,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
        "sliding_window": WINDOW, "num_dense_layers": 2, "num_experts": 8,
        "num_experts_per_tok": 2, "num_shared_experts": 1,
        "moe_intermediate_size": 32, "intermediate_size": 96,
        "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
        "mup_enabled": True, "rope_theta": 10000, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": False, "hidden_act": "silu", "n_group": 1,
        "topk_group": 1, "num_expert_groups": 1, "num_limited_groups": 1,
        "rope_scaling": None}
SIZES = dict(vocab_size=96, d_model=48, n_layers=8, n_heads=4,
             max_seq=MAX_SEQ, dtype="float32", seed=0)
SHARE = dict(SIZES, arch=ARCH, experts_held=4, first_expert=0)
WHOLE = dict(SIZES, arch=ARCH)
GREEDY = dict(temperature=0.0)
WAIT_S = 120.0

ref = manifest.load_module("benchmark/reference/trinity.py")

#: name -> (prompt tokens, answer tokens): a prompt shorter than the window;
#: one whose answer crosses the window's edge in decode steps; one that has
#: wrapped the ring more than twice inside a bucket padded to 64.
REGIMES = {"shorter_than_the_window": (5, 6),
           "crossing_the_window_in_decode": (12, 10),
           "wrapped_twice_in_a_padded_bucket": (41, 20)}


def prompt_of(n: int, seed: int = 0) -> list:
    return np.random.default_rng(seed).integers(1, 96, size=n).tolist()


@pytest.fixture(scope="module")
def engine():
    eng = ContinuousEngine(LLMConfig(**SHARE), max_batch=2, decode_chunk=4)
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


# ------------------------------------------------- engine against reference
@pytest.mark.parametrize("regime", list(REGIMES))
def test_prefill_then_cached_decode_give_the_references_logits(
        engine, reference, regime):
    """The engine's own programs, one after the other as the scheduler
    issues them (a prefill padded to its bucket, the hand-over of its
    slices into batch row 1, single-token steps through the cache under a
    `kv_bound`), against the reference's full forward pass, logit by
    logit."""
    plen, n = REGIMES[regime]
    prompt = prompt_of(plen)
    lb = engine._bucket(plen)
    toks = np.zeros((1, lb), np.int32)
    toks[0, :plen] = prompt
    last, slices = engine._prefill(engine.params, jnp.asarray(toks), plen)
    for leaf in jax.tree.leaves(slices):
        assert leaf.shape[1] in (lb, min(lb, WINDOW))
    mirrors = (engine._toks_dev, engine._lens_dev, engine._keys,
               engine._temps_dev, engine._topks_dev, engine._topps_dev)
    first = jnp.argmax(last).astype(jnp.int32)
    cache, mirrors = engine._place(
        engine._init_cache(), slices, mirrors, first, engine._keys[0],
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
    want = reference(prompt + served)[np.arange(n) + plen - 1]
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4)


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
    """`LLMEngine.generate` (no bound, whole rings, an unpadded prefill
    longer than the window) on the same parameters."""
    eng = LLMEngine(LLMConfig(**SHARE, params={"params": engine.params}))
    assert eng.model.cfg == model_config(LLMConfig(**SHARE))
    prompt = prompt_of(21, seed=2)
    out = eng.generate(np.asarray([prompt]), 9)[0].tolist()
    assert gaps_of(reference, prompt, out[21:]).max() < 1e-3


# ------------------------------------------------ a slot's later occupants
@pytest.mark.parametrize("later", ["short", "wrapped"])
def test_a_later_occupant_sees_none_of_an_earlier_ones_rows(later, reference):
    """One batch row, so every request is seated where the last one left
    its rows: a long request fills the full leaves and wraps the rings,
    then a short one (which reaches only some rows of a ring) or another
    wrapped one takes the row."""
    eng = ContinuousEngine(LLMConfig(**SHARE), max_batch=1, decode_chunk=4)
    try:
        first = prompt_of(41, seed=3)
        assert gaps_of(reference, first,
                       alone(eng, first, max_tokens=20)).max() < 1e-3
        nxt = prompt_of(5 if later == "short" else 37, seed=4)
        toks = alone(eng, nxt, max_tokens=14)
        assert gaps_of(reference, nxt, toks).max() < 1e-3
        assert eng.cache_stats()["splices"] == 2
    finally:
        eng.shutdown()


@pytest.mark.parametrize("newcomer", ["short", "wrapped"])
def test_a_request_spliced_behind_a_chunk_in_flight_gets_its_own_tokens(
        newcomer, reference, monkeypatch):
    """A request that stops early gives its row up while chunks that still
    step it are in flight; the next request's hand-over (full leaves and
    rings alike) queues behind them and its tokens are the reference's.
    (No chunk is read until the newcomer is parked, so the stop is read
    with the pipeline full.)"""
    eng = ContinuousEngine(LLMConfig(**SHARE), max_batch=2, decode_chunk=4)
    gate, drain = threading.Event(), eng._drain
    monkeypatch.setattr(
        eng, "_drain", lambda ph: (gate.wait(WAIT_S), drain(ph))[1])
    gate.set()
    try:
        stopper = prompt_of(38, seed=5)
        base = alone(eng, stopper, max_tokens=24)
        stop = base[5]
        cut = base.index(stop) + 1
        long_p = prompt_of(9, seed=6)
        nxt = prompt_of(4 if newcomer == "short" else 33, seed=7)
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
        eng.shutdown()


def test_the_prefill_lane_parks_no_more_than_its_budget(monkeypatch):
    """Parked cache slices live on the device: with one batch row and
    requests of the largest bucket, the lane runs ahead only while what is
    parked fits a quarter of the cache's bytes (or is one request)."""
    eng = ContinuousEngine(LLMConfig(**SHARE), max_batch=1, decode_chunk=4)
    try:
        one = eng._slice_bytes(64)
        assert eng._park_budget < 2 * one  # so at most one may be parked
        most, splice = [0], eng._splice

        def spy(*args):
            most[0] = max(most[0], len(eng._ready) + 1)
            return splice(*args)

        monkeypatch.setattr(eng, "_splice", spy)
        streams = [eng.submit(prompt_of(40, seed=i),
                              SamplingParams(**GREEDY, max_tokens=6))
                   for i in range(5)]
        assert all(len(s.tokens()) == 6 for s in streams)
        assert most[0] == 1 and eng._parked_bytes == 0
    finally:
        eng.shutdown()


@pytest.mark.parametrize("plen, max_seq, rows", [
    (5, 8192, 8), (2040, 8192, 2048), (2049, 8192, 4096), (4096, 8192, 4096),
    (4097, 8192, 6144), (5000, 8192, 6144), (6144, 8192, 6144),
    (6145, 8192, 8192), (7000, 8192, 8192), (3000, 4096, 4096),
    (9000, 16384, 12288), (5000, 6000, 6000)])
def test_a_prompt_is_padded_to_a_power_of_two_or_three_quarters_of_a_long_one(
        plen, max_seq, rows):
    """Above 4096 rows a bucket comes at three quarters of a power of two as
    well; below it, and for every older configuration (their caches end at
    2048 and 4096 rows), the buckets are the powers of two they were."""
    eng = object.__new__(ContinuousEngine)
    eng.cfg = LLMConfig(**dict(SIZES, max_seq=max_seq))
    assert eng._bucket(plen) == rows


# ------------------------------------------------------- prefill attention
@pytest.mark.parametrize("rows", [64, 48])
@pytest.mark.parametrize("window", [0, 5, 16, 40])
def test_tiled_prefill_attention_is_the_masked_dense_one(window, rows,
                                                         monkeypatch):
    """Query tiles over the call's own rows, grouped by key/value head, a
    band for a window: against the full [S, S] mask, with tiles so small
    that a band starts inside the sequence. A call of three quarters of a
    power of two (the bucket of 6144) goes in tiles of a power of two."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "SCORE_TILE_BYTES", 4 * 8 * 64 * 4)
    keys = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(keys[0], (2, rows, 4, 16), jnp.float32)
    k = jax.random.normal(keys[1], (2, rows, 2, 16), jnp.float32)
    v = jax.random.normal(keys[2], (2, rows, 2, 16), jnp.float32)
    got = prefill_attention(q, k, v, window)
    back = jnp.arange(rows)[:, None] - jnp.arange(rows)[None, :]
    seen = (back >= 0) & (back < window) if window else back >= 0
    scores = jnp.einsum("bshd,bthd->bhst", q, jnp.repeat(k, 2, 2)) / 4.0
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    want = jnp.einsum("bhst,bthd->bshd", probs, jnp.repeat(v, 2, 2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------------------------------------- the shares
@pytest.mark.parametrize("serving", [False, True], ids=["dense", "grouped"])
def test_the_two_shares_add_up_to_the_uncut_layer_of_the_reference(serving):
    """Two chips of 4 experts each: their partial sums, with the shared
    expert (which both compute alike) counted once, are what the plain
    reference gives for the layer with all 8 experts."""
    whole = model_config(LLMConfig(**WHOLE))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 40, 48), jnp.float32)
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
@pytest.mark.parametrize("key, value", [
    ("model_type", "mistral"), ("n_group", 2), ("topk_group", 2),
    ("num_expert_groups", 2), ("num_limited_groups", 4),
    ("rope_scaling", {"type": "yarn", "factor": 4}), ("hidden_act", "gelu"),
    ("attention_bias", True),
    ("layer_types", ["sliding_attention"] * 7),
    ("layer_types", ["chunked_attention"] * 8),
    ("num_key_value_heads", 3)])
def test_model_config_refuses_what_it_does_not_build(key, value):
    with pytest.raises(ValueError):
        model_config(LLMConfig(**dict(SHARE, arch=dict(ARCH, **{key: value}))))


def test_model_config_refuses_experts_outside_the_published():
    with pytest.raises(ValueError):
        model_config(LLMConfig(**dict(SHARE, first_expert=5)))


def test_model_config_reads_every_published_key_of_the_new_arm():
    cfg = model_config(LLMConfig(**SHARE))
    assert cfg == TransformerConfig(
        vocab_size=96, d_model=48, n_layers=8, n_heads=4, max_seq=MAX_SEQ,
        dtype=jnp.dtype("float32"), n_kv_heads=2, head_size=16, d_ff=96,
        rope_theta=10000.0, norm_eps=1e-5, tie_embeddings=False,
        sliding_window=WINDOW, window_layers=(True, True, True, False) * 2,
        rope_window_only=True, qk_norm=True, attn_gate=True,
        sandwich_norm=True, emb_scale=48 ** 0.5, moe_experts=8, moe_top_k=2,
        moe_d_ff=32, moe_scoring="sigmoid", moe_norm_topk=True,
        moe_routed_scale=2.826, moe_score_bias=True, moe_shared_experts=1,
        moe_first_layer=2, experts_held=4, first_expert=0)
    assert cfg.head_dim == 16
    assert [cfg.window_of(i) for i in range(8)] == [16, 16, 16, 0] * 2
    assert [cfg.is_moe_layer(i) for i in range(8)] == [False] * 2 + [True] * 6


@pytest.mark.parametrize("name", ["phi3", "kimi"])
def test_the_older_arms_build_what_they_built_field_for_field(name):
    """The fields the new block brought keep their defaults for the two
    configurations the benchmark already runs."""
    with open(os.path.join(ROOT, "benchmark", "configs", {
            "phi3": "phi3-mini-16l", "kimi": "kimi-k2-ep32-6l"}[name]
            + ".json")) as f:
        llm = json.load(f)["llm_config"]
    sizes = dict(vocab_size=llm["vocab_size"], d_model=llm["d_model"],
                 n_layers=llm["n_layers"], n_heads=llm["n_heads"],
                 max_seq=llm["max_seq"], dtype=jnp.dtype("bfloat16"))
    want = {"phi3": dict(n_kv_heads=32, d_ff=8192),
            "kimi": dict(
                n_kv_heads=64, d_ff=18432, rope_theta=50000.0, norm_eps=1e-5,
                tie_embeddings=False, mixers=("mla",) * 6, q_lora_rank=1536,
                kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                v_head_dim=128, rope_yarn=YarnScaling(
                    factor=64, original_max_position_embeddings=4096,
                    beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1),
                moe_experts=384, moe_top_k=8, moe_d_ff=2048,
                moe_scoring="sigmoid", moe_norm_topk=True,
                moe_routed_scale=2.827, moe_score_bias=True,
                moe_shared_experts=1, moe_first_layer=1, experts_held=12,
                first_expert=0)}[name]
    cfg = model_config(LLMConfig(**llm))
    assert cfg == TransformerConfig(**sizes, **want)
    assert cfg.head_dim == {"phi3": 96, "kimi": 112}[name]
    assert not any(cfg.window_of(i) for i in range(cfg.n_layers))


def test_a_pipeline_stage_refuses_a_model_with_window_layers():
    """`llm/pipeline.py` keeps one kind of leaf; a wrong cache is not
    built."""
    mcfg = model_config(LLMConfig(**SHARE))
    with pytest.raises(NotImplementedError, match="window"):
        make_stage_net(mcfg, (0, 1, 2, 3), True, False)
    # a stage of a model without windows is built as before
    make_stage_net(model_config(LLMConfig(**SIZES)), (0, 1), True, False)


# ----------------------------------------------- stats, spans and counters
def test_the_stats_name_both_kinds_of_leaf(engine):
    alone(engine, prompt_of(20, seed=8), max_tokens=9)
    st = engine.cache_stats()
    row = 2 * 16 * 4  # key/value heads x head size x float32
    assert st["kv_heads"] == 2 and st["cache_kind"] == "kv"
    kinds = st["cache_kinds"]
    assert {k: (v["layers"], v["rows"], v["bytes"])
            for k, v in kinds.items()} == {
        "full": (2, MAX_SEQ, 2 * 2 * 2 * MAX_SEQ * row),
        "window": (6, WINDOW, 6 * 2 * 2 * WINDOW * row)}
    assert st["cache_bytes"] == sum(v["bytes"] for v in kinds.values())
    assert st["cache_boundary_copies"] == 0
    for kind in kinds.values():
        assert 0 < kind["live_share"] <= kind["walk_share"] <= 1
    # the shares at the top are the full leaves'; a ring fills sooner
    assert st["kv_walk_share"] == kinds["full"]["walk_share"]
    assert kinds["window"]["walk_share"] > kinds["full"]["walk_share"]
    assert (st["experts_held"], st["experts_published"]) == (4, 8)


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


def test_a_chunks_span_carries_the_rows_walked_and_visible_by_kind(
        engine, spans):
    tracing._ctx.set(("3" * 32, "4" * 16))
    stream = engine.submit(prompt_of(10, seed=9), SamplingParams(
        temperature=0.7, top_k=8, max_tokens=14))
    tracing._ctx.set(None)
    assert len(stream.tokens()) == 14
    deadline = time.monotonic() + WAIT_S
    while engine.num_active and time.monotonic() < deadline:
        time.sleep(0.01)
    chunks = [s["at"] for s in spans if s["n"] == "engine.dispatch_chunk"]
    assert chunks
    for at in chunks:
        bound, n = at["kv_bound"], at["tokens"]
        assert at["kv_rows_full"] == at["kv_rows"] == kv_prefix_rows(
            bound, MAX_SEQ)
        assert at["kv_rows_window"] == kv_prefix_rows(bound, WINDOW)
        # one live slot: step j of the chunk sees bound - n + j + 1 rows
        seen = np.arange(bound - n + 1, bound + 1)
        assert at["kv_live_full"] == pytest.approx(seen.mean(), abs=0.01)
        assert at["kv_live_window"] == pytest.approx(
            np.minimum(seen, WINDOW).mean(), abs=0.01)
        assert at["kv_live_window"] <= at["kv_rows_window"] <= WINDOW
    assert any(at["kv_bound"] > WINDOW for at in chunks)
    counted = [s["at"] for s in spans if s["n"] == "engine.host_sync"
               and s["at"].get("moe_steps")]
    # (13 steps follow the first token; the scheduler may step once more)
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
