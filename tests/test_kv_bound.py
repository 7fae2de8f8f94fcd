"""A decode step's attention stops at the longest LIVE context (README
"Serving hot loop"): `ops/decode_attention.py` `over_kv_prefix` reads a
static prefix of the cache chosen by one `lax.switch` from a `kv_bound` that
the engine's scheduler hands down, the only place that knows which slots
are live.

Here: the helper against the dense masked attention over the whole cache
(K and V per head, grouped heads, a latent row); the same prefix picked on
the host and inside the program; callers that pass no bound lower to the
programs they lowered to before the bound existed; and the scheduler's
bound over a run of mixed lengths. All on the CPU at tiny sizes."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig, LLMEngine
from ray_tpu.llm.engine import ContinuousEngine, SamplingParams
from ray_tpu.models.transformer import Transformer, TransformerConfig
from ray_tpu.ops.decode_attention import (_xla_decode_attention,
                                          decode_attention, kv_prefix_rows,
                                          kv_prefixes, over_kv_prefix)

MAX_SEQ = 64
SLOTS = 4
ENDS = kv_prefixes(MAX_SEQ)  # quarters: 16, 32, 48 and 64 rows

#: name -> (each slot's rows, the bound, the slots whose output is used).
#: An idle slot's device-side length is stale and may lie beyond the bound:
#: what it computes is discarded, so it may be anything.
CASES = {
    "ragged": ([3, 17, 9, 30], 33, [0, 1, 2, 3]),
    "bound_on_a_prefix_edge": ([ENDS[1], 5, 16, 8], ENDS[1], [0, 1, 2, 3]),
    "bound_of_one": ([1, 1, 1, 1], 1, [0, 1, 2, 3]),
    "bound_of_max_seq": ([64, 2, 41, 57], 64, [0, 1, 2, 3]),
    "stale_idle_slot_beyond_the_bound": ([5, 60, 9, 12], 14, [0, 2, 3]),
}

MLA = dict(mixers=("mla", "mla"), q_lora_rank=24, kv_lora_rank=16,
           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
BASE = dict(vocab_size=64, d_model=48, n_layers=2, n_heads=4, n_kv_heads=4,
            d_ff=64, max_seq=MAX_SEQ, dtype=jnp.float32)


def heads_case(kind: str, lengths, bound):
    """(bounded, whole-cache) outputs [slots, heads, dim] of the decode
    attention op for K and V per head; rows of the cache wider than the
    head, as the engine makes them on the chip."""
    hq, hkv, d, row = (4, 4, 12, 16) if kind == "mha" else (8, 2, 12, 16)
    keys = jax.random.split(jax.random.PRNGKey(len(kind)), 3)
    q = jax.random.normal(keys[0], (SLOTS, hq, d), jnp.float32)
    k = jax.random.normal(keys[1], (SLOTS, MAX_SEQ, hkv, row), jnp.float32)
    v = jax.random.normal(keys[2], (SLOTS, MAX_SEQ, hkv, row), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    got = jax.jit(lambda kb: decode_attention(q, k, v, lens, kv_bound=kb))(
        jnp.int32(bound))
    return got, _xla_decode_attention(q, k[..., :d], v[..., :d], lens)


def latent_case(lengths, bound):
    """(bounded, whole-cache) logits [slots, vocab] of one decode step of a
    latent-attention model over a cache of random latents: the bound goes
    Transformer -> Block -> MLA -> over_kv_prefix."""
    model = Transformer(TransformerConfig(**BASE, **MLA))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    toks = jnp.arange(SLOTS, dtype=jnp.int32)[:, None] + 1
    # the step writes row `length - 1` and sees rows [0, length)
    pos = jnp.asarray(lengths, jnp.int32)[:, None] - 1
    shapes = jax.eval_shape(
        lambda: model.apply({"params": params}, toks, positions=pos,
                            decode=True, mutable=["cache"])[1]["cache"])
    leaves, tree = jax.tree.flatten(shapes)
    cache = jax.tree.unflatten(tree, [
        jax.random.normal(jax.random.PRNGKey(i), leaf.shape, leaf.dtype)
        for i, leaf in enumerate(leaves)])

    def step(kb):
        return model.apply({"params": params, "cache": cache}, toks,
                           positions=pos, decode=True, kv_bound=kb,
                           mutable=["cache"])[0][:, 0]

    return jax.jit(step)(jnp.int32(bound)), step(None)


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("kind", ["mha", "gqa", "latent"])
def test_a_bounded_walk_is_the_whole_walk_for_every_live_slot(kind, case):
    """The rows a bounded step leaves out are rows whose softmax weight the
    per-slot mask makes exactly zero, so a live slot's output is the whole
    cache's (to the last bit is expected; 1e-6 is demanded)."""
    lengths, bound, live = CASES[case]
    got, want = (latent_case(lengths, bound) if kind == "latent"
                 else heads_case(kind, lengths, bound))
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=0, atol=1e-6)


def test_host_and_program_pick_the_same_prefix_for_every_bound():
    """`kv_prefix_rows` (the engine's counter and span) against the branch
    `over_kv_prefix` takes: each branch reports the rows it was given."""
    leaf = jnp.zeros((2, MAX_SEQ, 3), jnp.float32)
    rows = jax.jit(lambda kb: over_kv_prefix(
        lambda lf: jnp.int32(lf.shape[1]), (leaf,), kb))
    assert ENDS[-1] == MAX_SEQ and list(ENDS) == sorted(set(ENDS))
    assert kv_prefixes(100)[-1] == 100 and len(kv_prefixes(100)) == len(ENDS)
    for bound in range(1, MAX_SEQ + 1):
        want = min(t for t in ENDS if t >= bound)
        assert kv_prefix_rows(bound, MAX_SEQ) == want
        assert int(rows(jnp.int32(bound))) == want


# The decode step of four tiny models as the parent commit of the PR that
# brought the bound lowered it: sha256 (first 16 hex digits) of
# `jax.jit(step).lower(...).as_text()`, taken there with this very function.
# A caller that passes no bound (LLMEngine.generate, the pipeline's stages,
# training) must keep lowering to that text. Whoever changes the model's
# decode step on purpose takes the digests again.
UNBOUNDED = {
    "mha": (TransformerConfig(**BASE), "a5f3b67665c9472b"),
    "mha_wide_rows": (TransformerConfig(**BASE, cache_row=16),
                      "7c95a0dcef79ff73"),
    "gqa": (TransformerConfig(**{**BASE, "n_kv_heads": 2}),
            "bcd56b9b24d5b8a5"),
    "mla": (TransformerConfig(**BASE, **MLA), "305dcf98b0c9028c"),
}


def step_text(cfg, bounded: bool, b: int = 3) -> str:
    model = Transformer(cfg)
    toks = jnp.zeros((b, 1), jnp.int32)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    cache = jax.eval_shape(
        lambda p: model.apply({"params": p}, toks, positions=toks,
                              decode=True, mutable=["cache"])[1]["cache"],
        params)

    def step(p, c, t, pos, *kb):
        return model.apply({"params": p, "cache": c}, t, positions=pos,
                           decode=True, mutable=["cache"],
                           **({"kv_bound": kb[0]} if kb else {}))

    extra = (jax.ShapeDtypeStruct((), jnp.int32),) if bounded else ()
    return jax.jit(step).lower(params, cache, toks, toks, *extra).as_text()


@pytest.mark.parametrize("name", list(UNBOUNDED))
def test_without_a_bound_the_step_lowers_to_the_program_it_always_was(name):
    cfg, digest = UNBOUNDED[name]
    text = step_text(cfg, bounded=False)
    assert "stablehlo.case" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
    # and with one, the layers' attention is ONE function of the module,
    # a switch over the prefixes, called once a layer
    bounded = step_text(cfg, bounded=True)
    walk = ("_latent_walk" if cfg.mixer_of(0) == "mla" else "grouped_walk"
            if cfg.n_kv_heads < cfg.n_heads else "_xla_decode_walk")
    assert bounded.count("stablehlo.case") == 1
    assert bounded.count(f"call @{walk}(") == cfg.n_layers


# ---------------------------------------------------------------------------
# The scheduler's bound.

CFG = LLMConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                max_seq=MAX_SEQ, dtype="float32")


def test_every_chunk_is_bounded_by_its_live_slots_in_a_run_of_mixed_lengths():
    """For every chunk the scheduler dispatches: the bound covers every live
    slot's rows after the chunk's n steps, is the least such number, and
    fits the cache; however many bounds a run sees, the chunk programs are
    one per (n, greedy); the counters of /v1/stats follow; and the greedy
    tokens are those of the whole-cache engine (`LLMEngine.generate`)."""
    eng = ContinuousEngine(CFG, max_batch=3, decode_chunk=4)
    seen = []
    chunk = eng._chunk

    def spy(params, cache, toks, lens, keys, temp, top_k, top_p, n, greedy,
            kv_bound, live_rows):
        # live: seated and still in need of a step (a cover chunk steps the
        # others past an occupant whose known last step is in flight)
        going = [s is not None and s.remaining - s.in_flight > 0
                 for s in eng._slots]
        live = [int(eng._lengths[i]) for i, on in enumerate(going) if on]
        assert live_rows.tolist() == going
        seen.append((live, n, int(kv_bound)))
        return chunk(params, cache, toks, lens, keys, temp, top_k, top_p, n,
                     greedy, kv_bound, live_rows)

    eng._chunk = spy
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 128, size=n).tolist()
                   for n in (5, 40, 17, 30, 9, 3, 33, 12, 21, 7)]
        budgets = [12, 6, 9, 20, 5, 16, 8, 3, 11, 14]
        streams = [eng.submit(p, SamplingParams(max_tokens=m,
                                                temperature=0.0))
                   for p, m in zip(prompts, budgets)]
        outs = [s.tokens() for s in streams]
        stats = eng.cache_stats()
    finally:
        eng.shutdown()
    assert len(seen) > 10
    for live, n, bound in seen:
        assert live and all(length + n <= bound for length in live)
        assert bound == max(live) + n <= MAX_SEQ
    # (the table's chunk programs: n = 1, 2, 4, every request greedy, and the
    # longest sampled one, which the engine reads the cache's layout from)
    built = {key for key in eng._programs.keys() if key[0] == "chunk"}
    assert built <= {("chunk", n, True) for n in (1, 2, 4)} | {
        ("chunk", 4, False)}
    assert len({bound for _l, _n, bound in seen}) > len(built)
    steps = sum(n for _l, n, _b in seen)
    walked = sum(n * kv_prefix_rows(b, MAX_SEQ) for _l, n, b in seen)
    lived = sum(n * (sum(live) / len(live) + (n + 1) / 2)
                for live, n, _b in seen)
    assert stats["kv_walk_share"] == pytest.approx(walked / (steps * MAX_SEQ))
    assert stats["kv_live_share"] == pytest.approx(lived / (steps * MAX_SEQ))
    assert 0 < stats["kv_live_share"] < stats["kv_walk_share"] < 1
    ref = LLMEngine(LLMConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, max_seq=MAX_SEQ,
        dtype="float32", params={"params": eng.params}))
    for prompt, m, out in zip(prompts, budgets, outs):
        want = ref.generate(np.asarray([prompt]), m)[0, len(prompt):]
        assert out == want.tolist()
