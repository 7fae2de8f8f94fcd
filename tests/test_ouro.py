"""A looped stack on the serving path (`model_type` `ouro`: Ouro), at a tiny
size on the CPU: `tiny-loop`, 2 layers run THREE times a token (so that no
count can pass by being 4) with one set of weights and three pairs of cache
leaves a layer, a norm and an exit gate between the passes, 4 heads of 16,
128 positions a slot. The plain reference is the benchmark's
(`benchmark/reference/ouro.py`), written from the equations and sharing only
the parameter tree's names with the program."""

import hashlib
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
from ray_tpu.models.transformer import (Transformer,  # noqa: E402
                                        TransformerConfig, param_specs)

PASSES, MAX_SEQ, VOCAB = 3, 128, 256
with open(os.path.join(ROOT, "benchmark", "tests", "configs",
                       "tiny-loop.json")) as _f:
    LLM = json.load(_f)["llm_config"]
ARCH = LLM["arch"]
GREEDY = dict(temperature=0.0)
WAIT_S = 120.0

ref = manifest.load_module("benchmark/reference/ouro.py")

#: name -> (prompt tokens, answer tokens): prompts inside a padded bucket
#: (19 of 32 rows), at a bucket's edge, and one row short of it; every answer
#: decodes across the bucket's end through the cache.
REGIMES = {"inside_a_padded_bucket": (19, 24),
           "at_the_buckets_edge": (32, 12),
           "one_row_short_of_the_edge": (31, 12),
           "shorter_than_the_least_bucket": (3, 14)}


def prompt_of(n: int, seed: int = 0) -> list:
    return np.random.default_rng(seed).integers(1, VOCAB, size=n).tolist()


@pytest.fixture(scope="module")
def engine():
    eng = ContinuousEngine(LLMConfig(**LLM), max_batch=4, decode_chunk=4)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def reference(engine):
    """prompt + tokens -> the reference's (logits [S, V], exit distribution
    [S, T]) on the engine's own parameters."""
    run = ref.build(LLM).run
    return lambda seq: tuple(map(np.asarray, run(
        engine.params, np.asarray(seq, np.int32))))


def gaps_of(reference, prompt, toks):
    rows = reference(prompt + toks)[0][np.arange(len(toks))
                                       + len(prompt) - 1]
    return rows.max(-1) - rows[np.arange(len(toks)), toks]


def alone(eng, prompt, **sampling):
    return eng.submit(prompt, SamplingParams(**GREEDY, **sampling)).tokens()


# ------------------------------------------------- engine against reference
@pytest.mark.parametrize("regime", list(REGIMES))
def test_prefill_then_cached_decode_give_the_references_logits_and_exits(
        engine, reference, regime):
    """The engine's own programs, one after the other as the scheduler
    issues them (a prefill padded to its bucket, the hand-over of ALL its
    slices into batch row 1, single-token steps through every pass's own
    leaves under a bound), against the reference's full forward pass, logit
    by logit and exit probability by exit probability: passes that shared a
    leaf, a norm outside the loop, a stale row or a padded position in ANY
    pass's leaf would each move them."""
    plen, n = REGIMES[regime]
    prompt = prompt_of(plen)
    lb = engine._bucket(plen)
    toks = np.zeros((1, lb), np.int32)
    toks[0, :plen] = prompt
    last, slices = engine._prefill(engine.params, jnp.asarray(toks), plen)
    handed = jax.tree.leaves(slices)
    assert len(handed) == 2 * PASSES * LLM["n_layers"]
    assert {leaf.shape[1] for leaf in handed} == {lb}
    # (the prefill program hands on no exit distribution: the same call,
    # asked for it)
    _logits, sown = engine.model.apply(
        {"params": engine.params}, jnp.asarray(toks),
        positions=jnp.arange(lb)[None], decode=True,
        prompt_len=jnp.asarray([plen]), mutable=["cache", "loop"])
    exits = [np.asarray(sown["loop"]["exit_p"][0, plen - 1])]
    mirrors = (engine._toks_dev, engine._lens_dev, engine._keys,
               engine._temps_dev, engine._topks_dev, engine._topps_dev)
    first = jnp.argmax(last).astype(jnp.int32)
    # a cache full of what an earlier occupant might have left: nothing of
    # it may be seen, in any pass's leaf
    dirty = jax.tree.map(lambda z: jnp.full_like(z, 3.0),
                         engine._init_cache())
    cache, mirrors = engine._place(
        dirty, slices, mirrors, first, engine._keys[0],
        np.array([1, plen, 0], np.int32), np.array([0.0, 1.0], np.float32))
    live = jnp.arange(4) == 1
    step = jax.jit(lambda cache, tok, pos, kb: engine.model.apply(
        {"params": engine.params, "cache": cache}, tok[:, None],
        positions=pos[:, None], decode=True, kv_bound=kb, live=live,
        mutable=["cache", "loop"]))
    got, served = [np.asarray(last)], [int(first)]
    tok, pos = mirrors[0], mirrors[1]
    for j in range(n - 1):
        logits, out = step(cache, tok, pos, jnp.int32(plen + j + 1))
        cache = out["cache"]
        got.append(np.asarray(logits[1, 0]))
        exits.append(np.asarray(out["loop"]["exit_p"][1, 0]))
        tok, pos = jnp.argmax(logits[:, 0], -1).astype(jnp.int32), pos + 1
        served.append(int(tok[1]))
    assert plen + n - 1 > lb or plen < 8  # the steps cross the bucket's end
    want, left = reference(prompt + served)
    at = np.arange(n) + plen - 1
    np.testing.assert_allclose(np.stack(got), want[at], atol=1e-4)
    np.testing.assert_allclose(np.stack(exits), left[at], atol=1e-4)
    assert left.shape[1] == PASSES
    np.testing.assert_allclose(left.sum(-1), 1.0, atol=1e-5)
    assert left.min() > 1e-3  # a gate that says something after every pass


@pytest.mark.parametrize("regime", list(REGIMES))
def test_served_greedy_tokens_are_the_references_best(engine, reference,
                                                      regime):
    """Through the scheduler: buckets, splices, chunks of 4, 2 and 1."""
    plen, n = REGIMES[regime]
    prompt = prompt_of(plen, seed=1)
    toks = alone(engine, prompt, max_tokens=n)
    assert len(toks) == n
    assert gaps_of(reference, prompt, toks).max() < 1e-4


def test_the_whole_sequence_form_is_the_references(engine, reference):
    """`decode=False` (a training batch): logits and exit distribution."""
    seq = prompt_of(101, seed=3)
    got, sown = engine.model.apply({"params": engine.params},
                                   jnp.asarray(seq)[None], mutable=["loop"])
    want, left = reference(seq)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sown["loop"]["exit_p"][0]), left,
                               atol=1e-4)


@pytest.mark.parametrize("degrade", ref.DEGRADES)
def test_a_degraded_reference_is_not_the_model(engine, reference, degrade):
    """What sets the cell's tolerance on the chip fails the tests' here:
    passes that share one pass's rows, a norm outside the loop, a cache or
    weights held in float8."""
    prompt = prompt_of(40, seed=2)
    toks = alone(engine, prompt, max_tokens=24)
    assert gaps_of(reference, prompt, toks).max() < 1e-4
    other, left = map(np.asarray, ref.build(LLM, degrade).run(
        engine.params, np.asarray(prompt + toks, np.int32)))
    want, exits = reference(prompt + toks)
    assert np.abs(other - want).max() > 1e-2
    assert np.abs(left - exits).max() > 1e-3
    if degrade == "shared_rows":
        # position 0 attends over itself alone, and every pass made that
        # row from the same token: the first row differs only from pass 2 on
        assert np.abs(other - want)[1:].max() > 0.1


def test_the_plain_llm_engine_serves_the_same_model(engine, reference):
    """`LLMEngine.generate` (no bound, every pass's leaves walked whole, an
    unpadded prefill)."""
    eng = LLMEngine(LLMConfig(**LLM, params={"params": engine.params}))
    assert eng.model.cfg == model_config(LLMConfig(**LLM))
    prompt = prompt_of(21, seed=5)
    out = eng.generate(np.asarray([prompt]), 20)[0].tolist()
    assert gaps_of(reference, prompt, out[21:]).max() < 1e-4


def test_slots_at_different_depths_each_get_what_they_get_alone(engine,
                                                                reference):
    prompts = [prompt_of(n, seed=10 + n) for n in (28, 61, 3, 40)]
    lone = [alone(engine, p, max_tokens=20) for p in prompts]
    streams = [engine.submit(p, SamplingParams(**GREEDY, max_tokens=20))
               for p in prompts]
    assert [s.tokens() for s in streams] == lone
    for prompt, toks in zip(prompts, lone):
        assert gaps_of(reference, prompt, toks).max() < 1e-4


def test_a_later_occupant_sees_none_of_an_earlier_ones_rows(reference):
    """One batch row: a long request fills every pass's leaves, then a
    shorter one takes the row."""
    eng = ContinuousEngine(LLMConfig(**LLM), max_batch=1, decode_chunk=4)
    try:
        first = prompt_of(70, seed=6)
        assert gaps_of(reference, first,
                       alone(eng, first, max_tokens=30)).max() < 1e-4
        nxt = prompt_of(5, seed=7)
        assert gaps_of(reference, nxt,
                       alone(eng, nxt, max_tokens=40)).max() < 1e-4
        assert eng.cache_stats()["splices"] == 2
    finally:
        eng.shutdown()


def test_under_a_tp_mesh_every_passes_leaves_are_sharded_over_heads(
        reference):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    eng = ContinuousEngine(LLMConfig(**LLM), max_batch=2, decode_chunk=4,
                           mesh=mesh)
    try:
        leaves = jax.tree.leaves(eng._cache_spec)
        assert len(leaves) == 2 * PASSES * LLM["n_layers"]
        assert {leaf.sharding.spec for leaf in leaves} == {
            jax.sharding.PartitionSpec(None, None, "tp", None)}
        gate = eng.params["exit_gate"]
        assert all(leaf.sharding.is_fully_replicated
                   for leaf in jax.tree.leaves(gate))
        prompt = prompt_of(41, seed=8)
        toks = alone(eng, prompt, max_tokens=20)
        assert gaps_of(reference, prompt, toks).max() < 1e-4
    finally:
        eng.shutdown()


# ------------------------------------------------------ the trees, the loop
@pytest.mark.parametrize("passes", [1, 2, PASSES])
def test_the_parameter_tree_has_each_layer_once_whatever_the_passes(passes):
    """One set of weights, `2 x passes` cache leaves a layer; the gate only
    where there is a loop, and replicated."""
    cfg = model_config(LLMConfig(**dict(LLM, arch=dict(
        ARCH, total_ut_steps=passes))))
    assert cfg.ut_steps == passes
    model = Transformer(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert sorted(shapes) == sorted(
        ["tok_emb", "lm_head", "final_norm", "layer_0", "layer_1"]
        + ["exit_gate"] * (passes > 1))
    once = model_config(LLMConfig(**dict(LLM, arch=dict(
        ARCH, total_ut_steps=1))))
    want = jax.eval_shape(lambda: Transformer(once).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert {k: v for k, v in shapes.items() if k != "exit_gate"} == want
    if passes > 1:
        assert {k: v.shape for k, v in shapes["exit_gate"].items()} == {
            "kernel": (64,), "bias": ()}
        specs = param_specs({"params": shapes})["params"]["exit_gate"]
        assert all(spec == jax.sharding.PartitionSpec()
                   for spec in jax.tree.leaves(specs))
    toks = jnp.zeros((2, 1), jnp.int32)
    cache = jax.eval_shape(
        lambda p: model.apply({"params": p}, toks, positions=toks,
                              decode=True, mutable=["cache"])[1]["cache"],
        shapes)
    names = ["k", "v"] + [f"{kv}_{t}" for t in range(1, passes)
                          for kv in "kv"]
    for i in range(2):
        assert sorted(cache[f"layer_{i}"]["attn"]) == sorted(names)
        assert [cfg.cache_kind_of(i, leaf) for leaf in names] == [
            "full"] * (2 * passes)
    assert {leaf.shape for leaf in jax.tree.leaves(cache)} == {
        (2, MAX_SEQ, 4, 16)}


#: sha256 (first 16 hex digits) of the lowered text of a sandwich-norm
#: model's decode step and prefill as the PARENT commit of the PR that
#: brought the loop lowered them (taken there with these very functions):
#: with `ut_steps` 1 the model is the program it always was.
PARENT_TEXTS = {"step": "6d89e76792469dee", "bounded_step": "f6466692d411b690",
                "prefill": "08bcc084edc01350"}
SANDWICH = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                n_kv_heads=4, d_ff=96, max_seq=64, dtype=jnp.float32,
                sandwich_norm=True, tie_embeddings=False, head_size=16,
                rope_theta=1e6)


def lowered_text(cfg, what: str) -> str:
    model = Transformer(cfg)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    if what == "prefill":
        def prefill(p, t, plen):
            return model.apply(
                {"params": p}, t, positions=jnp.arange(16)[None],
                decode=True, prompt_len=jnp.reshape(plen, (1,)),
                mutable=["cache"])

        return jax.jit(prefill).lower(
            params, jnp.zeros((1, 16), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    toks = jnp.zeros((3, 1), jnp.int32)
    cache = jax.eval_shape(
        lambda p: model.apply({"params": p}, toks, positions=toks,
                              decode=True, mutable=["cache"])[1]["cache"],
        params)

    def step(p, c, t, pos, *kb):
        return model.apply({"params": p, "cache": c}, t, positions=pos,
                           decode=True, mutable=["cache"],
                           **({"kv_bound": kb[0]} if kb else {}))

    extra = ((jax.ShapeDtypeStruct((), jnp.int32),)
             if what == "bounded_step" else ())
    return jax.jit(step).lower(params, cache, toks, toks, *extra).as_text()


@pytest.mark.parametrize("what", list(PARENT_TEXTS))
def test_one_pass_is_the_sandwich_norm_model_it_always_was(what):
    """Bit for bit: the same program text from the same parameters."""
    text = lowered_text(TransformerConfig(**SANDWICH), what)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_TEXTS[
        what]
    assert TransformerConfig(**SANDWICH).ut_steps == 1
    looped = lowered_text(TransformerConfig(**SANDWICH, ut_steps=2), what)
    assert looped != text and "while" not in looped  # written out, not rolled


def test_one_pass_gives_the_sandwich_norm_models_logits_bit_for_bit(engine):
    """`total_ut_steps` 1 on the looped model's own layers, norm and head:
    the model the repository had, and the first pass's state is what the
    loop goes on from."""
    once = model_config(LLMConfig(**dict(LLM, arch=dict(
        ARCH, total_ut_steps=1))))
    plain = TransformerConfig(**{**SANDWICH, "max_seq": MAX_SEQ,
                                 "rope_theta": 1000000.0})
    assert once == plain
    params = {k: v for k, v in engine.params.items() if k != "exit_gate"}
    seq = jnp.asarray(prompt_of(30, seed=4))[None]
    got = Transformer(once).apply({"params": params}, seq)
    want = Transformer(plain).apply({"params": params}, seq)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    looped = engine.model.apply({"params": engine.params}, seq)
    assert np.abs(np.asarray(looped) - np.asarray(want)).max() > 1e-2


def test_a_loop_over_another_mixer_is_refused():
    cfg = TransformerConfig(**{**SANDWICH, "mixers": ("mla", "mla")},
                            ut_steps=2, kv_lora_rank=16, qk_nope_head_dim=8,
                            qk_rope_head_dim=8, v_head_dim=8)
    with pytest.raises(NotImplementedError, match="looped stack"):
        Transformer(cfg).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32))


# ------------------------------------------------------------ model_config
@pytest.mark.parametrize("key, value", [
    ("early_exit_threshold", 0.9), ("use_sliding_window", True),
    ("sliding_window", 1024), ("hidden_act", "gelu"),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("attention_bias", True), ("total_ut_steps", 0),
    ("layer_types", ["full_attention"]),
    ("layer_types", ["full_attention", "sliding_attention"]),
    ("num_key_value_heads", 3), ("num_key_value_heads", 0)])
def test_model_config_refuses_what_it_does_not_build(key, value):
    with pytest.raises(ValueError, match="not built"):
        model_config(LLMConfig(**dict(LLM, arch=dict(ARCH, **{key: value}))))


def test_model_config_reads_every_published_key_of_the_new_arm():
    cfg = model_config(LLMConfig(**LLM))
    assert cfg == TransformerConfig(
        vocab_size=VOCAB, d_model=64, n_layers=2, n_heads=4, max_seq=MAX_SEQ,
        dtype=jnp.dtype("float32"), n_kv_heads=4, head_size=16, d_ff=96,
        rope_theta=1000000.0, norm_eps=1e-6, tie_embeddings=False,
        sandwich_norm=True, ut_steps=PASSES)
    # a key that bears on nothing while no layer has a window
    assert cfg == model_config(LLMConfig(**dict(LLM, arch=dict(
        ARCH, max_window_layers=48))))
    grouped = model_config(LLMConfig(**dict(LLM, arch=dict(
        ARCH, num_key_value_heads=2))))
    assert grouped.n_kv_heads == 2


def test_the_published_configuration_is_built_at_its_widths():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b-8l.json")) as f:
        config = json.load(f)
    cfg = model_config(LLMConfig(**config["llm_config"]))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab_size, cfg.ut_steps, cfg.n_layers, cfg.max_seq,
            cfg.rope_theta, cfg.norm_eps, cfg.tie_embeddings,
            cfg.sandwich_norm) == (2048, 16, 16, 128, 5632, 49152, 4, 8, 2048,
                                   1e6, 1e-6, False, True)
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "max_position_embeddings"]
    changed = {k for k, v in config["published"].items() if config[k] != v}
    assert changed == set(config["reduced"])
    assert config["total_ut_steps"] == 4
    assert config["early_exit_threshold"] == 1
    arch = config["llm_config"]["arch"]
    assert all(arch[k] == config[k] for k in arch)
    assert len(config["assumed"]) >= 8


def test_a_pipeline_stage_refuses_a_looped_stack():
    mcfg = model_config(LLMConfig(**LLM))
    with pytest.raises(NotImplementedError, match="LAST stage's output"):
        make_stage_net(mcfg, (0, 1), True, True)


# ----------------------------------------------- stats, spans and counters
def test_the_stats_say_the_passes_and_count_them(engine):
    before = engine.cache_stats()
    toks = alone(engine, prompt_of(40, seed=9), max_tokens=30)
    assert len(toks) == 30
    st = engine.cache_stats()
    row = 4 * 16 * 4  # heads x head size x float32
    assert st["kv_heads"] == 4 and st["cache_kind"] == "kv"
    full = st["cache_kinds"]["full"]
    assert (full["layers"], full["leaves"], full["passes"], full["rows"],
            full["bytes"]) == (2, 2 * PASSES * 2, PASSES, MAX_SEQ,
                               2 * PASSES * 2 * 4 * MAX_SEQ * row)
    assert list(st["cache_kinds"]) == ["full"]
    assert 0 < full["live_share"] <= full["walk_share"] <= 1
    assert st["ut_steps"] == PASSES
    steps = st["decode_steps"] - before["decode_steps"]
    # one occupant: its live steps x the passes each ran
    assert (st["loop_passes_total"] - before["loop_passes_total"]
            == steps * PASSES)
    mass = np.subtract(st["exit_mass_total"], before["exit_mass_total"])
    assert len(mass) == PASSES and (mass > 0).all()
    assert mass.sum() == pytest.approx(steps, abs=0.05)


def test_another_models_stats_and_spans_say_nothing_of_a_loop():
    eng = ContinuousEngine(LLMConfig(
        vocab_size=VOCAB, d_model=64, n_layers=2, n_heads=4, max_seq=MAX_SEQ,
        dtype="float32"), max_batch=2, decode_chunk=4)
    try:
        alone(eng, prompt_of(9), max_tokens=6)
        st = eng.cache_stats()
        assert "passes" not in st["cache_kinds"]["full"]
        assert not {"ut_steps", "loop_passes_total", "exit_mass_total"} & set(
            st)
    finally:
        eng.shutdown()


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


def test_a_chunks_exit_mass_sums_to_its_live_slot_steps(engine, spans):
    """Two occupants: each chunk's `exit_mass` has one number a
    pass and sums to the steps of the slots that were live in it; its
    `loop_passes` is those x the passes; the rows stay ONE leaf's."""
    tracing._ctx.set(("5" * 32, "6" * 16))
    other = engine.submit(prompt_of(12, seed=12), SamplingParams(
        temperature=0.7, top_k=8, max_tokens=60))
    stream = engine.submit(prompt_of(26, seed=11), SamplingParams(
        temperature=0.7, top_k=8, max_tokens=44))
    tracing._ctx.set(None)
    assert len(stream.tokens()) == 44 and len(other.tokens()) == 60
    deadline = time.monotonic() + WAIT_S
    while engine.num_active and time.monotonic() < deadline:
        time.sleep(0.01)
    prefill = next(s["at"] for s in spans if s["n"] == "engine.prefill")
    assert (prefill["bucket"], prefill["ut_steps"]) == (16, PASSES)
    chunks = {s["at"]["seq"]: s["at"] for s in spans
              if s["n"] == "engine.dispatch_chunk"}
    assert chunks and all(at["ut_steps"] == PASSES and at["attention"] == "xla"
                          for at in chunks.values())
    for at in chunks.values():
        assert at["kv_live_full"] <= at["kv_rows_full"] <= MAX_SEQ
        assert at["kv_live_full"] <= at["kv_bound"]  # one leaf's rows
    counted = [s["at"] for s in spans if s["n"] == "engine.host_sync"
               and "exit_mass" in s["at"]]
    assert counted
    for at in counted:
        live_steps = at["tokens"] * chunks[at["seq"]]["active"]
        assert len(at["exit_mass"]) == PASSES
        assert sum(at["exit_mass"]) == pytest.approx(live_steps, abs=0.01)
        assert at["loop_passes"] == live_steps * PASSES
    assert {chunks[at["seq"]]["active"] for at in counted} >= {1, 2}


def test_the_loops_parts_carry_their_names_in_the_program(engine):
    import re

    engine._cache = engine._cache or engine._init_cache()
    text = engine._chunk.lower(
        engine.params, engine._cache, engine._toks_dev, engine._lens_dev,
        engine._keys, engine._temps_dev, engine._topks_dev,
        engine._topps_dev, 2, False, jnp.int32(9),
        np.ones(4, bool)).compile().as_text()
    names = re.findall(r'op_name="([^"]+)"', text)
    scopes = {part for name in names for part in name.split("/")}
    assert {"exit_distribution", "exit_gate", "decode_attention", "mlp",
            "lm_head", "sampler"} <= scopes
    # (that the step's body holds no loop of its own is read from the
    # program compiled for the chip, `tests/test_v5e_compile.py`: the CPU's
    # compiler writes its sampler's sort as loops)
