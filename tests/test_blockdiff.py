"""Generation by diffusion over blocks (`model_type: sdar_moe`; README
"Serving hot loop"): a forward carries a whole block a slot, several
forwards finish a block, and only the last one's cache rows stay.

Here, on the CPU at tiny widths and seeded weights: the served path
(`ContinuousEngine`, greedy) against the plain reference's OWN generation
(`benchmark/reference/sdar.py`, written from the issue's equations, no
cache) token for token, and the model's logits against the reference's; the
prefill by blocks and the block step's attention against the full mask, in
their XLA forms and in the kernels' interpret mode; the sampler's
probability; what `model_config` builds and refuses. What the TPU's compiler
makes of the chunk program is tests/test_v5e_compile.py's."""

import functools
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from ray_tpu.llm import LLMConfig, LLMEngine
from ray_tpu.llm.engine import ContinuousEngine, SamplingParams
from ray_tpu.llm.sampler import _make_sampler
from ray_tpu.models.published import model_config
from ray_tpu.models.transformer import Denoising, Transformer
from ray_tpu.ops import attention
from ray_tpu.ops.flash_attention import block_causal_attention

da = importlib.import_module("ray_tpu.ops.decode_attention")
sdar = manifest.load_module("benchmark/reference/sdar.py")

MASK = 250
ARCH = {"model_type": "sdar_moe", "num_key_value_heads": 2, "head_dim": 16,
        "attention_bias": False, "rope_theta": 1e6, "rope_scaling": None,
        "use_sliding_window": False, "sliding_window": None,
        "max_window_layers": 2, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "norm_topk_prob": True,
        "tie_word_embeddings": False, "hidden_act": "silu",
        "rms_norm_eps": 1e-6, "num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "intermediate_size": 96,
        "block_length": 4, "denoising_steps": 4,
        "remasking_strategy": "low_confidence_dynamic",
        "confidence_threshold": 0.9, "mask_token_id": MASK}


def llm(**arch) -> dict:
    return dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                max_seq=64, dtype="float32", seed=0, arch={**ARCH, **arch})


def prompt_of(n: int, seed: int = 0) -> list:
    return np.random.default_rng(seed).integers(0, 200, n).tolist()


@pytest.fixture(scope="module")
def params():
    return sdar.served_params(llm())


@pytest.fixture(scope="module")
def forward():
    return sdar.build(llm()).forward


def served(cfg: dict, params, requests, *, max_batch=4, decode_chunk=8,
           gaps=()):
    """`[(tokens, finish_reason)]` of `requests` [(prompt, max_tokens)]
    through one engine, and its stats; `gaps[i]` seconds before request i."""
    eng = ContinuousEngine(LLMConfig(**{**cfg, "params": params}),
                           max_batch=max_batch, decode_chunk=decode_chunk)
    try:
        streams = []
        for i, (prompt, n) in enumerate(requests):
            time.sleep(gaps[i] if i < len(gaps) else 0.0)
            streams.append(eng.submit(prompt, SamplingParams(
                temperature=0.0, max_tokens=n)))
        out = [(s.tokens(), s.finish_reason) for s in streams]
        return out, eng.cache_stats()
    finally:
        eng.shutdown()


# ------------------------------------------------------------ the served path
@pytest.mark.parametrize("plen,n", [
    (8, 8),    # P mod 4 = 0: the first block is all mask
    (9, 10),   # P mod 4 = 1: the first block opens with a prompt token
    (14, 7),   # P mod 4 = 2, N not a multiple of 4: the last block is cut
    (11, 5),   # P mod 4 = 3: one position of the first block to fill
    (3, 6),    # P < 4: nothing is prefilled
    (1, 1),
])
def test_a_lone_request_is_served_the_references_own_generation(
        plen, n, params, forward):
    prompt = prompt_of(plen, seed=plen)
    want = sdar.generate(llm(), params, prompt, n, width=64, forward=forward)
    [(tokens, finish)], stats = served(llm(), params, [(prompt, n)])
    assert tokens == want["tokens"] and finish == "length"
    # a forward a denoising step and one a block committed, slot by slot
    blocks = -(-(plen % 4 + n) // 4)
    assert stats["bd_commits_total"] == blocks
    assert stats["bd_forwards_total"] == want["forwards"] + blocks
    assert stats["bd_tokens_total"] == n
    assert stats["bd_freed_total"] == sum(map(len, want["freed"]))
    assert stats["cache_kinds"]["full"]["block_length"] == 4


def test_requests_of_different_depth_and_phase_share_a_forward(
        params, forward):
    """Four slots, six requests: two wait for a row another leaves, and are
    seated mid-stream into rows whose cache holds an earlier answer."""
    requests = [(prompt_of(p, seed=100 + p), n) for p, n in
                [(9, 10), (3, 5), (8, 13), (14, 7), (6, 9), (21, 3)]]
    got, stats = served(llm(), params, requests)
    for (prompt, n), (tokens, finish) in zip(requests, got):
        want = sdar.generate(llm(), params, prompt, n, width=64,
                             forward=forward)
        assert tokens == want["tokens"] and finish == "length", (len(prompt), n)
    assert stats["splices"] == 6
    assert stats["bd_tokens_total"] == sum(n for _p, n in requests)


def test_a_request_seated_mid_chunk_into_a_row_another_left(params, forward):
    """One slot: every request but the first takes the row its predecessor
    left, while chunks that stepped the predecessor may still be in flight."""
    requests = [(prompt_of(p, seed=200 + p), n) for p, n in
                [(10, 6), (5, 9), (16, 4)]]
    got, stats = served(llm(), params, requests, max_batch=1,
                        gaps=(0.0, 0.3, 0.0))
    for (prompt, n), (tokens, _finish) in zip(requests, got):
        assert tokens == sdar.generate(llm(), params, prompt, n, width=64,
                                       forward=forward)["tokens"]
    assert stats["splices"] == 3


@pytest.mark.parametrize("arch", [
    # a threshold low enough that forwards free 2, 3 and 4 positions
    {"confidence_threshold": 0.0058},
    {"confidence_threshold": 0.0062},
    {"remasking_strategy": "low_confidence_static", "denoising_steps": 2},
    {"remasking_strategy": "low_confidence_static", "denoising_steps": 3},
], ids=["tau_0.0058", "tau_0.0062", "static_2_steps", "static_3_steps"])
def test_blocks_of_two_to_five_forwards_in_one_batch(arch, params):
    cfg = llm(**arch)
    forward = sdar.build(cfg).forward
    requests = [(prompt_of(p, seed=300 + p), n) for p, n in
                [(9, 12), (4, 16), (7, 9), (12, 8)]]
    got, stats = served(cfg, params, requests)
    sizes = set()
    for (prompt, n), (tokens, _finish) in zip(requests, got):
        want = sdar.generate(cfg, params, prompt, n, width=64,
                             forward=forward)
        assert tokens == want["tokens"], (len(prompt), n)
        sizes |= {len(f) for f in want["freed"]}
    if "confidence_threshold" in arch:
        assert sizes == {1, 2, 3, 4}, sizes  # blocks of 2 to 5 forwards
    else:
        assert max(sizes) == 2  # 4 positions in 2 or 3 steps
    assert stats["bd_tokens_total"] == sum(n for _p, n in requests)
    assert stats["bd_forwards_total"] < 5 * stats["bd_commits_total"]


def test_a_stop_token_inside_a_block_cuts_the_answer_there(params, forward):
    prompt = prompt_of(9, seed=9)
    want = sdar.generate(llm(), params, prompt, 12, width=64,
                         forward=forward)["tokens"]
    stop = want[5]
    eng = ContinuousEngine(LLMConfig(**{**llm(), "params": params}),
                           max_batch=2, decode_chunk=8)
    try:
        stream = eng.submit(prompt, SamplingParams(
            temperature=0.0, max_tokens=12, stop_token=stop))
        tokens = stream.tokens()
    finally:
        eng.shutdown()
    assert tokens == want[:want.index(stop) + 1]
    assert stream.finish_reason == "stop"


def test_the_replay_accepts_what_was_served_and_fails_a_degraded_cache(
        params):
    """`check` as the benchmark calls it: the served tokens replay with no
    gap to speak of, and the same tokens against keys and values rounded to
    float8 do not."""
    requests = [(prompt_of(21, seed=21), 40), (prompt_of(14, seed=30), 45)]
    got, _stats = served(llm(), params, requests)
    cases = [(p, tokens) for (p, _n), (tokens, _f) in zip(requests, got)]
    sdar.served_params = lambda cfg, _tree=params: _tree
    good = sdar.check(llm(), cases)
    assert all(r["finite"] for r in good["rows"])
    assert max(r["max_gap"] for r in good["rows"]) < 1e-3
    assert all(r["argmax_matches"] == r["freed"] for r in good["rows"])
    wrong = [(p, [(t + 1) % 200 for t in tokens]) for p, tokens in cases]
    assert min(r["max_gap"] for r in sdar.check(llm(), wrong)["rows"]) > 0.1
    low = sdar.check(llm(), cases, degrade="kv_float8")
    assert max(r["max_gap"] for r in low["rows"]) > max(
        r["max_gap"] for r in good["rows"])


def test_the_models_logits_are_the_references(params, forward):
    """`Transformer.apply` over a whole sequence (visibility by blocks, no
    cache) against the reference's forward, masks among the tokens."""
    tokens = prompt_of(29, seed=1) + [MASK] * 3
    net = Transformer(model_config(LLMConfig(**llm())))
    got = net.apply({"params": params}, jnp.asarray([tokens], jnp.int32))[0]
    want = forward(params, np.asarray(tokens, np.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=0)


# ----------------------------------------------------- the prefill by blocks
def full_mask_attention(q, k, v, blocks, lens=None):
    """q [B, S, H, D] against k, v [B, T, KV, D] under the full mask
    `j // L <= i // L` (or, a slot `lens`: keys [0, lens[b]) for every query),
    float32."""
    b, s, h, d = q.shape
    t, kv = k.shape[1], k.shape[2]
    k = jnp.repeat(k.astype(jnp.float32), h // kv, axis=2)
    v = jnp.repeat(v.astype(jnp.float32), h // kv, axis=2)
    scores = jnp.einsum("bqhd,bthd->bhqt", q.astype(jnp.float32), k) / d ** .5
    if lens is None:
        visible = (jnp.arange(t)[None, :] // blocks
                   <= jnp.arange(s)[:, None] // blocks)[None, None]
    else:
        visible = (jnp.arange(t)[None, :] < lens[:, None])[:, None, None, :]
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
    return jnp.einsum("bhqt,bthd->bqhd", probs, v)


@pytest.mark.parametrize("form", ["xla", "xla_tiles", "kernel"])
def test_prefill_by_blocks_is_the_full_mask_at_a_bucket_past_the_prompt(
        form, monkeypatch):
    """A bucket of 256 rows whose prompt ends at 148: rows past it are
    padding (keys of NaN and values of 1e30 here: none may reach a live
    row)."""
    s, plen, h, kv, d = 256, 148, 4, 2, 128
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, s, h, d), jnp.float32)
    k, v = (jax.random.normal(key, (1, s, kv, d), jnp.float32)
            for key in keys[1:])
    want = full_mask_attention(q, k, v, 4)[:, :plen]
    # (a weight of exactly 0 keeps a finite value out of a sum)
    pad = (jnp.arange(s) >= plen)[None, :, None, None]
    k, v = jnp.where(pad, jnp.nan, k), jnp.where(pad, 1e30, v)
    if form == "kernel":
        got = block_causal_attention(
            q, k, v, blocks=4, q_len=jnp.asarray([plen]), block_q=64,
            block_k=128, interpret=True)
    else:
        if form == "xla_tiles":  # several tiles: the loop over a padded K
            monkeypatch.setattr(attention, "SCORE_TILE_BYTES", 64 << 10)
        got = attention.dot_product_attention(
            q, k, v, causal=True, blocks=4, q_len=jnp.asarray([plen]))
    np.testing.assert_allclose(np.asarray(got[:, :plen]), np.asarray(want),
                               atol=2e-5, rtol=0)


def test_the_kernels_rule_refuses_what_it_cannot_tile(monkeypatch):
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    q, k = (1, 256, 32, 128), (1, 256, 4, 128)
    assert attention.kernel_refusal(q, k, blocks=4) is None
    assert "lane" in attention.kernel_refusal(
        (1, 256, 32, 96), (1, 256, 4, 96), blocks=4)
    assert "whole blocks of 3" in attention.kernel_refusal(q, k, blocks=3)
    with pytest.raises(ValueError, match="visibility by blocks"):
        attention.dot_product_attention(
            jnp.zeros(q), jnp.zeros(k), jnp.zeros(k), window=8, blocks=4)


# ------------------------------------------------- the block step's attention
#: (the ragged kernel's row block and the piece of a slot's last block)
ROWS, BLOCK, GRANULE = 64, 16, 4


@pytest.mark.parametrize("form", ["walk", "kernel"])
@pytest.mark.parametrize("committed", [
    [0, 12, 16, 60],   # nothing yet; a block that ENDS a row block; one
                       # that begins one; the leaf's last rows
    [4, 8, 28, 44],    # pieces' edges: stops at 8, 12, 32 and 48
    [20, 36, 0, 52],
])
def test_the_block_step_sees_its_committed_rows_and_its_own_block(
        form, committed, monkeypatch):
    """L = 4 queries a slot, ONE stop a slot (committed + 4): against the
    full mask over the slot's rows. Rows past a stop are an earlier
    occupant's (NaN here), and a free slot's are never read."""
    b, size, h, kv, d = 4, 4, 8, 2, 128
    live = jnp.asarray([True, True, True, False])
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (b, size, h, d), jnp.bfloat16)
    k, v = (jax.random.normal(key, (b, ROWS, kv, d), jnp.bfloat16)
            for key in keys[1:])
    stop = jnp.asarray(committed) + size
    want = full_mask_attention(q, k, v, size, lens=stop)
    past = (jnp.arange(ROWS)[None, :] >= stop[:, None])[..., None, None]
    past = past | ~live[:, None, None, None]
    k, v = jnp.where(past, jnp.nan, k), jnp.where(past, jnp.nan, v)
    if form == "kernel":
        monkeypatch.setattr(attention, "on_tpu", lambda: True)
        monkeypatch.setattr(da, "ragged_decode_attention", functools.partial(
            da.ragged_decode_attention, interpret=True))
        row_bytes = kv * d * 2
        monkeypatch.setattr(da, "BLOCK_BYTES", BLOCK * row_bytes)
        monkeypatch.setattr(da, "GRANULE_BYTES", GRANULE * row_bytes)
        assert da.row_block(k.shape, k.dtype) == BLOCK
        assert da.row_granule(k.shape, k.dtype) == GRANULE
    if form == "walk":  # (no kernel reads a free slot; the walk masks it)
        k, v = jnp.nan_to_num(k), jnp.nan_to_num(v)
    got = da.block_decode_attention(
        q, k, v, stop, kv_bound=jnp.int32(int(stop[:3].max())), live=live)
    np.testing.assert_allclose(
        np.asarray(got[:3], np.float32), np.asarray(want[:3]), atol=3e-2,
        rtol=0)
    if form == "kernel":
        assert not np.asarray(got[3], np.float32).any()  # a free slot: zeros


# ------------------------------------------------------------------ sampler
@pytest.mark.parametrize("top_k", [0, 5])
def test_the_samplers_probability_is_the_softmax_written_out(top_k):
    vocab, rows = 64, 6
    logits = jax.random.normal(jax.random.PRNGKey(2), (rows, vocab)) * 3
    temp = jnp.asarray([0.0, 0.0, 0.7, 0.7, 1.3, 1.3])
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(rows, dtype=jnp.uint32))
    sample = _make_sampler(vocab)
    token, prob = sample(logits, keys, temp, jnp.full((rows,), top_k),
                         jnp.ones((rows,)), with_prob=True)
    assert (np.asarray(token) == np.asarray(sample(
        logits, keys, temp, jnp.full((rows,), top_k), jnp.ones((rows,))))).all()
    logits, token = np.asarray(logits, np.float64), np.asarray(token)
    for r in range(rows):
        if temp[r] <= 0:  # greedy: the argmax under the plain softmax
            row = logits[r]
            assert token[r] == row.argmax()
        else:  # the kept, tempered distribution
            row = logits[r] / float(temp[r])
            if top_k:
                row = np.where(row >= np.sort(row)[-top_k], row, -np.inf)
        want = np.exp(row[token[r]] - row.max()) / np.exp(row - row.max()).sum()
        assert np.isfinite(row[token[r]])
        np.testing.assert_allclose(float(prob[r]), want, rtol=1e-5)


# ------------------------------------------------------------- model_config
def test_model_config_builds_the_published_keys():
    cfg = model_config(LLMConfig(**llm()))
    assert (cfg.block_length, cfg.n_kv_heads, cfg.head_dim) == (4, 2, 16)
    assert cfg.denoising == Denoising(4, "low_confidence_dynamic", 0.9, MASK)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_d_ff) == (8, 2, 32)
    assert cfg.moe_scoring == "softmax" and cfg.moe_norm_topk
    assert not cfg.moe_score_bias and not cfg.moe_shared_experts
    assert cfg.qk_norm and not cfg.tie_embeddings and not cfg.mixers
    assert cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-6
    assert all(cfg.is_moe_layer(i) for i in range(cfg.n_layers))
    assert cfg.denoising.counts(4) == (1, 1, 1, 1)
    assert Denoising(3, "low_confidence_static", 0.9, 0).counts(4) == (2, 1, 1)
    assert cfg.denoising.forwards(4, 4) == 5
    assert cfg.denoising.forwards(4, 3) == 4
    assert cfg.denoising.forwards(4, 2, done=2) == 3


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("use_sliding_window", True),
    ("sliding_window", 4096),
    ("decoder_sparse_step", 2),
    ("mlp_only_layers", [0]),
    ("norm_topk_prob", False),
    ("tie_word_embeddings", True),
    ("hidden_act", "gelu"),
    ("remasking_strategy", "random"),
    ("remasking_strategy", "entropy_bounded"),
    ("denoising_steps", 5),   # more steps than positions
    ("denoising_steps", 0),
    ("block_length", 5),      # 64 positions a slot are no whole blocks
    ("mask_token_id", 256),   # outside the vocabulary
    ("num_key_value_heads", 3),
])
def test_model_config_refuses_what_it_does_not_build(key, value):
    with pytest.raises(ValueError, match="not built|not among"):
        model_config(LLMConfig(**llm(**{key: value})))


def test_the_engines_that_step_a_token_refuse_a_model_of_blocks():
    from ray_tpu.llm.pipeline import make_stage_net

    with pytest.raises(NotImplementedError, match="blocks"):
        make_stage_net(model_config(LLMConfig(**llm())), (0, 1), True, True)
    with pytest.raises(NotImplementedError, match="blocks"):
        LLMEngine(LLMConfig(**llm()))
