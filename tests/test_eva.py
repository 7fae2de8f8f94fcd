"""EVA attention on the serving path (`model_type` `evabyte`: EvaByte), at a
tiny size on the CPU: `tiny-eva`, 2 layers, a window of 32 positions that
starts over, one summary row for every chunk of 4, 128 positions a slot, 4
heads of 16, a head of 8 x 64 columns of which the first 64 are read. The
plain reference is the benchmark's (`benchmark/reference/evabyte.py`),
written from the equations and sharing only the parameter tree's names with
the program."""

import dataclasses
import os
import re
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
from ray_tpu.models.transformer import (Transformer,  # noqa: E402
                                        TransformerConfig)
from ray_tpu.ops.decode_attention import (kv_prefix_rows,  # noqa: E402
                                          merge_partials, partial_walk)

WINDOW, CHUNK, MAX_SEQ, VOCAB = 32, 4, 128, 64
PER = WINDOW // CHUNK
ARCH = {"model_type": "evabyte", "attention_class": "eva",
        "chunk_size": CHUNK, "window_size": WINDOW, "num_chunks": None,
        "num_key_value_heads": 4, "intermediate_size": 96,
        "hidden_act": "silu", "attention_bias": False, "rope_theta": 100000,
        "rope_scaling": None, "rms_norm_eps": 1e-5,
        "norm_add_unit_offset": True, "fp32_skip_add": True,
        "fp32_logits": True, "num_pred_heads": 8,
        "tie_word_embeddings": False, "pool_init_std": 4.0}
LLM = dict(vocab_size=VOCAB, d_model=64, n_layers=2, n_heads=4,
           max_seq=MAX_SEQ, dtype="float32", seed=0, arch=ARCH)
GREEDY = dict(temperature=0.0)
WAIT_S = 120.0

ref = manifest.load_module("benchmark/reference/evabyte.py")

#: name -> (prompt tokens, answer tokens): prompts under, at and over a
#: window's edge, inside a chunk and at a chunk's edge, each padded to its
#: bucket (32, 64 or 128 rows); every answer decodes two windows further.
REGIMES = {"under_the_edge_inside_a_chunk": (30, 68),
           "at_the_edge": (32, 66),
           "over_the_edge_inside_a_chunk": (33, 66),
           "at_a_chunks_edge_in_the_second_window": (36, 66),
           "inside_a_chunk_in_a_padded_bucket": (37, 66),
           "in_the_first_window_shorter_than_a_chunk": (3, 70),
           "two_windows_behind_it": (70, 50)}


def prompt_of(n: int, seed: int = 0) -> list:
    return np.random.default_rng(seed).integers(1, VOCAB, size=n).tolist()


@pytest.fixture(scope="module")
def engine():
    eng = ContinuousEngine(LLMConfig(**LLM), max_batch=4, decode_chunk=4)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def reference(engine):
    """prompt + tokens -> the reference's logits [S, V] on the engine's own
    parameters."""
    run = ref.build(LLM).run
    return lambda seq: np.asarray(run(engine.params,
                                      np.asarray(seq, np.int32)))


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
    slices into batch row 1, single-token steps through both leaves under a
    bound), against the reference's full forward pass, logit by logit: a
    summary seen before its window's end, a stale window row or a padded
    position in a summary would each move them."""
    plen, n = REGIMES[regime]
    prompt = prompt_of(plen)
    lb = engine._bucket(plen)
    toks = np.zeros((1, lb), np.int32)
    toks[0, :plen] = prompt
    last, slices = engine._prefill(engine.params, jnp.asarray(toks), plen)
    assert {leaf.shape[1] for leaf in jax.tree.leaves(slices)} == {
        min(lb, WINDOW), min(max(1, lb // CHUNK), MAX_SEQ // CHUNK)}
    mirrors = (engine._toks_dev, engine._lens_dev, engine._keys,
               engine._temps_dev, engine._topks_dev, engine._topps_dev)
    first = jnp.argmax(last).astype(jnp.int32)
    # a cache full of what an earlier occupant might have left: nothing of
    # it may be seen
    dirty = jax.tree.map(lambda z: jnp.full_like(z, 3.0),
                         engine._init_cache())
    cache, mirrors = engine._place(
        dirty, slices, mirrors, first, engine._keys[0],
        np.array([1, plen, 0], np.int32), np.array([0.0, 1.0], np.float32))
    live = jnp.arange(4) == 1
    step = jax.jit(lambda cache, tok, pos, kb: engine.model.apply(
        {"params": engine.params, "cache": cache}, tok[:, None],
        positions=pos[:, None], decode=True, kv_bound=kb, live=live,
        mutable=["cache"]))
    got, served = [np.asarray(last)], [int(first)]
    tok, pos = mirrors[0], mirrors[1]
    for j in range(n - 1):
        logits, out = step(cache, tok, pos, jnp.int32(plen + j + 1))
        cache = out["cache"]
        got.append(np.asarray(logits[1, 0]))
        tok, pos = jnp.argmax(logits[:, 0], -1).astype(jnp.int32), pos + 1
        served.append(int(tok[1]))
    want = reference(prompt + served)[np.arange(n) + plen - 1]
    np.testing.assert_allclose(np.stack(got), want, atol=1e-4)
    assert want.shape[1] == VOCAB  # head 0 of the 8 held


@pytest.mark.parametrize("regime", list(REGIMES))
def test_served_greedy_tokens_are_the_references_best(engine, reference,
                                                      regime):
    """Through the scheduler: buckets, splices, chunks of 4, 2 and 1."""
    plen, n = REGIMES[regime]
    prompt = prompt_of(plen, seed=1)
    toks = alone(engine, prompt, max_tokens=n)
    assert len(toks) == n
    assert gaps_of(reference, prompt, toks).max() < 1e-4


@pytest.mark.parametrize("degrade", ["mean_pool", "kv_float8"])
def test_a_plain_mean_or_a_lower_precision_is_not_the_model(engine, reference,
                                                            degrade):
    """The pooling vectors are large enough that pooling by a plain mean is
    another function, by far more than the tests' tolerance."""
    seq = prompt_of(100, seed=2)
    other = np.asarray(ref.build(LLM, degrade).run(
        engine.params, np.asarray(seq, np.int32)))
    want = reference(seq)
    assert np.abs(other - want)[:WINDOW].max() < (
        1e-5 if degrade == "mean_pool" else np.inf)  # no summary seen yet
    assert np.abs(other - want)[WINDOW:].max() > 1e-2


def test_the_whole_sequence_form_is_the_references(engine, reference):
    """`decode=False` (a training batch), at a length that is no whole
    number of windows."""
    seq = prompt_of(101, seed=3)
    got = engine.model.apply({"params": engine.params},
                             jnp.asarray(seq)[None])[0]
    np.testing.assert_allclose(np.asarray(got), reference(seq), atol=1e-4)


def test_the_first_window_is_the_mha_layers_causal_attention(engine):
    """Within the first `window_size` positions no summary is seen: the
    layer IS plain causal attention, and the `mha` block on the same
    matrices (it has no `mu` and `phi`) gives the same logits."""
    mcfg = engine.model.cfg
    plain = Transformer(dataclasses.replace(mcfg, mixers=()))
    params = {k: ({**v, "attn": {n: w for n, w in v["attn"].items()
                                  if n not in ("mu", "phi")}}
                  if k.startswith("layer_") else v)
              for k, v in engine.params.items()}
    seq = jnp.asarray(prompt_of(WINDOW, seed=4))[None]
    want = plain.apply({"params": params}, seq)
    got = engine.model.apply({"params": engine.params}, seq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    longer = jnp.asarray(prompt_of(WINDOW + 8, seed=4))[None]
    assert np.abs(np.asarray(
        plain.apply({"params": params}, longer)
        - engine.model.apply({"params": engine.params}, longer)
    ))[0, WINDOW:].max() > 1e-3  # and no longer past it


def test_the_plain_llm_engine_serves_the_same_model(engine, reference):
    """`LLMEngine.generate` (no bound, both leaves walked whole, an
    unpadded prefill longer than the window)."""
    eng = LLMEngine(LLMConfig(**LLM, params={"params": engine.params}))
    assert eng.model.cfg == model_config(LLMConfig(**LLM))
    prompt = prompt_of(45, seed=5)
    out = eng.generate(np.asarray([prompt]), 40)[0].tolist()
    assert gaps_of(reference, prompt, out[45:]).max() < 1e-4


def test_two_leaves_merge_into_one_softmax():
    """`partial_walk` x 2 + `merge_partials` against the softmax over the
    rows of both leaves written out, free slot and empty leaf included."""
    rng = np.random.default_rng(0)
    b, h, d, rows_w, rows_c = 3, 2, 8, 16, 8
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    leaves = [jnp.asarray(rng.normal(size=(b, r, h, d)), jnp.float32)
              for r in (rows_w, rows_w, rows_c, rows_c)]
    stop_w, stop_c = jnp.array([5, 16, 0]), jnp.array([0, 6, 0])
    got = merge_partials(
        partial_walk(q, leaves[0], leaves[1], stop_w, jnp.max(stop_w)),
        partial_walk(q, leaves[2], leaves[3], stop_c, jnp.max(stop_c)))
    for i in range(b):
        k = np.concatenate([leaves[0][i, :stop_w[i]], leaves[2][i, :stop_c[i]]])
        v = np.concatenate([leaves[1][i, :stop_w[i]], leaves[3][i, :stop_c[i]]])
        if not len(k):
            assert not np.asarray(got[i]).any()  # a free slot: zeros
            continue
        s = np.einsum("hd,thd->ht", q[i], k) / d ** 0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("ht,thd->hd", p / p.sum(-1, keepdims=True), v)
        np.testing.assert_allclose(np.asarray(got[i]), want, atol=1e-5)


# ------------------------------------------------ slots beside one another
def test_slots_at_different_phases_each_get_what_they_get_alone(engine,
                                                                reference):
    """One continuous batch: a slot crossing a window's edge, one crossing
    a chunk's edge, one in its first window and one two windows in, each
    stepped by the same programs at its own phase."""
    prompts = [prompt_of(n, seed=10 + n) for n in (28, 61, 3, 70)]
    lone = [alone(engine, p, max_tokens=40) for p in prompts]
    streams = [engine.submit(p, SamplingParams(**GREEDY, max_tokens=40))
               for p in prompts]
    assert [s.tokens() for s in streams] == lone
    for prompt, toks in zip(prompts, lone):
        assert gaps_of(reference, prompt, toks).max() < 1e-4


@pytest.mark.parametrize("later", ["first_window", "second_window"])
def test_a_later_occupant_sees_none_of_an_earlier_ones_rows(later, reference):
    """One batch row, so every request is seated where the last one left
    its window rows and its summaries: a long request fills both leaves,
    then a shorter one takes the row."""
    eng = ContinuousEngine(LLMConfig(**LLM), max_batch=1, decode_chunk=4)
    try:
        first = prompt_of(70, seed=6)
        assert gaps_of(reference, first,
                       alone(eng, first, max_tokens=50)).max() < 1e-4
        nxt = prompt_of(5 if later == "first_window" else 37, seed=7)
        toks = alone(eng, nxt, max_tokens=60)
        assert gaps_of(reference, nxt, toks).max() < 1e-4
        assert eng.cache_stats()["splices"] == 2
    finally:
        eng.shutdown()


def test_under_a_tp_mesh_both_kinds_of_leaf_are_sharded_over_heads(reference):
    """The summaries are K and V of a kind: their head axis goes over `tp`
    as the window's does, never replicated, and the tokens are the
    reference's."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    eng = ContinuousEngine(LLMConfig(**LLM), max_batch=2, decode_chunk=4,
                           mesh=mesh)
    try:
        specs = {leaf.sharding.spec for leaf in jax.tree.leaves(
            eng._cache_spec)}
        assert specs == {jax.sharding.PartitionSpec(None, None, "tp", None)}
        prompt = prompt_of(41, seed=8)
        toks = alone(eng, prompt, max_tokens=30)
        assert gaps_of(reference, prompt, toks).max() < 1e-4
    finally:
        eng.shutdown()


# ------------------------------------------------------------ model_config
@pytest.mark.parametrize("key, value", [
    ("attention_class", "softmax"), ("num_chunks", 8),
    ("rope_scaling", {"type": "yarn", "factor": 4}), ("attention_bias", True),
    ("hidden_act", "gelu"), ("window_size", 30), ("chunk_size", 0),
    ("norm_add_unit_offset", False), ("fp32_skip_add", False),
    ("fp32_logits", False), ("tie_word_embeddings", True),
    ("num_key_value_heads", 2)])
def test_model_config_refuses_what_it_does_not_build(key, value):
    with pytest.raises(ValueError, match="not built"):
        model_config(LLMConfig(**dict(LLM, arch=dict(ARCH, **{key: value}))))


def test_model_config_refuses_slots_of_no_whole_number_of_windows():
    with pytest.raises(ValueError, match="whole windows"):
        model_config(LLMConfig(**dict(LLM, max_seq=100)))


def test_model_config_reads_every_published_key_of_the_new_arm():
    cfg = model_config(LLMConfig(**LLM))
    assert cfg == TransformerConfig(
        vocab_size=VOCAB, d_model=64, n_layers=2, n_heads=4, max_seq=MAX_SEQ,
        dtype=jnp.dtype("float32"), n_kv_heads=4, d_ff=96,
        rope_theta=100000.0, norm_eps=1e-5, tie_embeddings=False,
        mixers=("eva", "eva"), eva_window=WINDOW, eva_chunk=CHUNK,
        eva_pool_std=4.0, norm_unit_offset=True, residual_f32=True,
        pred_heads=8)
    assert [cfg.window_of(i) for i in range(2)] == [WINDOW] * 2
    assert [cfg.cache_kind_of(0, leaf) for leaf in
            ("k", "v", "kbar", "vbar")] == ["window"] * 2 + ["chunks"] * 2
    # the training-only keys are named in the file and change nothing
    assert cfg == model_config(LLMConfig(**dict(LLM, arch=dict(
        ARCH, mixedp_attn=True, lazy_init=True, init_fn="v2",
        init_std=0.01275, init_cutoff_factor=None))))


def test_the_published_configuration_is_built_at_its_widths():
    import json

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "evabyte-pp4-8l.json")) as f:
        config = json.load(f)
    cfg = model_config(LLMConfig(**config["llm_config"]))
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff, cfg.vocab_size,
            cfg.eva_window, cfg.eva_chunk, cfg.pred_heads, cfg.n_layers,
            cfg.max_seq) == (4096, 32, 128, 11008, 320, 2048, 16, 8, 8, 16384)
    assert config["reduced"] == ["num_hidden_layers",
                                 "max_position_embeddings", "max_seq_length"]
    changed = {k for k, v in config["published"].items() if config[k] != v}
    assert changed == set(config["reduced"])


def test_a_pipeline_stage_refuses_a_model_with_leaves_of_two_kinds():
    mcfg = model_config(LLMConfig(**LLM))
    with pytest.raises(NotImplementedError, match="one row a chunk"):
        make_stage_net(mcfg, (0, 1), True, False)


# ----------------------------------------------- stats, spans and counters
def test_the_stats_name_both_kinds_of_leaf_of_one_layer(engine):
    alone(engine, prompt_of(40, seed=9), max_tokens=30)
    st = engine.cache_stats()
    row = 4 * 16 * 4  # heads x head size x float32
    assert st["kv_heads"] == 4 and st["cache_kind"] == "kv"
    kinds = st["cache_kinds"]
    assert {k: (v["layers"], v["leaves"], v["rows"], v["bytes"])
            for k, v in kinds.items()} == {
        "window": (2, 4, WINDOW, 2 * 2 * 4 * WINDOW * row),
        "chunks": (2, 4, MAX_SEQ // CHUNK,
                   2 * 2 * 4 * (MAX_SEQ // CHUNK) * row)}
    assert st["cache_bytes"] == sum(v["bytes"] for v in kinds.values())
    for kind in kinds.values():
        assert 0 < kind["live_share"] <= kind["walk_share"] <= 1
    assert st["kv_walk_share"] == kinds["window"]["walk_share"]
    assert st["eva_summaries_total"] > 0 and st["eva_restarts_total"] > 0
    assert st["decode_steps_kernel"] == 0  # (off the chip: the two walks)


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


def test_a_chunks_span_carries_both_leaves_rows_and_the_sync_the_counters(
        engine, spans):
    """One traced request alone: prompt of 26, 44 tokens, so positions 26
    to 68 are stepped: the window starts over at 32 and 64, chunks end at
    27, 31, ..., 67."""
    before = engine.cache_stats()
    tracing._ctx.set(("5" * 32, "6" * 16))
    stream = engine.submit(prompt_of(26, seed=11), SamplingParams(
        temperature=0.7, top_k=8, max_tokens=44))
    tracing._ctx.set(None)
    assert len(stream.tokens()) == 44
    deadline = time.monotonic() + WAIT_S
    while engine.num_active and time.monotonic() < deadline:
        time.sleep(0.01)
    prefill = next(s["at"] for s in spans if s["n"] == "engine.prefill")
    assert (prefill["bucket"], prefill["windows"], prefill["summaries"]) == (
        32, 1, 26 // CHUNK)
    chunks = [s["at"] for s in spans if s["n"] == "engine.dispatch_chunk"]
    assert chunks and all(at["attention"] == "xla" for at in chunks)
    for at in chunks:
        bound, n = at["kv_bound"], at["tokens"]
        pos = np.arange(bound - n, bound)  # the positions stepped
        shown_w, shown_c = pos % WINDOW + 1, pos // WINDOW * PER
        assert at["kv_live_window"] == pytest.approx(shown_w.mean(), abs=.01)
        assert at["kv_live_chunks"] == pytest.approx(shown_c.mean(), abs=.01)
        assert at["kv_rows_window"] == pytest.approx(np.mean(
            [kv_prefix_rows(int(r), WINDOW) for r in shown_w]), abs=.01)
        assert at["kv_rows_chunks"] == pytest.approx(np.mean(
            [kv_prefix_rows(int(r), MAX_SEQ // CHUNK) for r in shown_c]),
            abs=.01)
        assert at["kv_live_window"] <= at["kv_rows_window"] <= WINDOW
        assert at["kv_rows"] == at["kv_rows_window"]
        assert "kv_rows_full" not in at
    assert any(at["kv_live_chunks"] == 2 * PER for at in chunks)
    counted = [s["at"] for s in spans if s["n"] == "engine.host_sync"
               and "eva_summaries" in s["at"]]
    steps = sum(a["tokens"] for a in counted)
    assert steps in (43, 44)  # the scheduler may step once more
    stepped = np.arange(26, 26 + steps)
    assert sum(a["eva_summaries"] for a in counted) == int(
        (stepped % CHUNK == CHUNK - 1).sum())
    assert sum(a["eva_restarts"] for a in counted) == 2
    st = engine.cache_stats()
    assert st["eva_restarts_total"] - before["eva_restarts_total"] == 2


def test_the_eva_parts_carry_their_names_in_the_program(engine):
    def scopes_of(lowered) -> set:
        names = re.findall(r'op_name="([^"]+)"',
                           lowered.compile().as_text())
        return {part for name in names for part in name.split("/")}

    engine._cache = engine._cache or engine._init_cache()
    chunk = engine._chunk.lower(
        engine.params, engine._cache, engine._toks_dev, engine._lens_dev,
        engine._keys, engine._temps_dev, engine._topks_dev,
        engine._topps_dev, 2, False, jnp.int32(9), np.ones(4, bool))
    assert {"eva_attention", "eva_summaries", "decode_attention", "mlp",
            "lm_head", "sampler"} <= scopes_of(chunk)
    prefill = engine._prefill.lower(
        engine.params, jnp.zeros((1, 64), jnp.int32), 40)
    assert {"eva_attention", "eva_summaries", "eva_window", "mlp",
            "lm_head"} <= scopes_of(prefill)
