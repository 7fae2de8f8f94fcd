"""The two-source prefill attention (README "Serving hot loop";
`ops/two_source_attention.py` `two_source_attention`): ONE blockwise Pallas
kernel for the whole-sequence call of an "eva" layer past its first window,
in which a query block meets its own window's rows up to its diagonal and
the summaries of the windows behind it, under one softmax, and no score
tile leaves the chip.

Here, on the CPU in interpret mode (tests/test_two_leaf_decode.py's way):
the kernel against the form it replaces on the chip (`models/eva.py`
`eva_sequence`, the tile scan) at two, three and six windows, with the
prompt ending inside a window, at a window's first row and at its last,
with everything no live query may see poisoned; the rule; the dispatcher,
which takes the tile scan off the chip and under differentiation; and the
engine serving the same tokens from a prefill through the kernel as from
one through the scan. What the TPU's compiler makes of it is
tests/test_v5e_compile.py's."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig
from ray_tpu.llm.engine import ContinuousEngine, SamplingParams
from ray_tpu.models import eva
from ray_tpu.ops import attention

ts = importlib.import_module("ray_tpu.ops.two_source_attention")
da = importlib.import_module("ray_tpu.ops.decode_attention")

#: Windows of 256 rows that hold 128 summaries each (a chunk of 2), in
#: query blocks of 64 rows against key blocks of 128: a window is four
#: query blocks and two key blocks, so a query block has key blocks wholly
#: before its diagonal and a diagonal one, as EvaByte's 512 x 1024 in 2048.
WINDOW, CHUNK, PER, HEADS, DIM = 256, 2, 128, 2, 128
BLOCK_Q, BLOCK_K = 64, 128

#: name -> q_len of a call of n windows
Q_LENS = {
    "inside_a_window": lambda n: (n - 1) * WINDOW + 83,
    "at_a_windows_first_row": lambda n: (n - 1) * WINDOW + 1,
    "at_a_windows_last_row": lambda n: (n - 1) * WINDOW,  # one window skipped
    "at_the_calls_last_row": lambda n: n * WINDOW,
}


def rows_of(windows: int, dtype, q_len: int):
    """(q, k, v, kbar, vbar) of a call of `windows` windows, and the same
    with everything no query below `q_len` may see set to NaN: the rows at
    and past `q_len` (the keys and values of later windows among them) and
    the summaries of the window that holds the last live row and of every
    later one."""
    s = windows * WINDOW
    keys = jax.random.split(jax.random.PRNGKey(windows), 5)
    q, k, v = (jax.random.normal(key, (1, s, HEADS, DIM), dtype)
               for key in keys[:3])
    mu, phi = (jax.random.normal(key, (HEADS, DIM)) * DIM ** -0.5
               for key in keys[3:])
    kbar, vbar = (t.astype(dtype) for t in eva.summaries(k, v, mu, phi,
                                                         CHUNK))
    clean = (q, k, v, kbar, vbar)
    seen = (q_len - 1) // WINDOW * PER  # summaries the last live row sees
    dirty = tuple(t.at[:, stop:].set(jnp.nan) for t, stop in zip(
        clean, (q_len, q_len, q_len, seen, seen)))
    return clean, dirty


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("ends", list(Q_LENS))
@pytest.mark.parametrize("windows", [2, 3, 6])
def test_the_kernel_is_the_tile_scan_and_reads_nothing_past_the_prompt(
        windows, ends, dtype):
    """The kernel on the poisoned rows against `eva_sequence` on the clean
    ones, below `q_len`: within the output's own rounding (one bf16 step;
    the sums are float32 in both forms), finite, and the same whether or
    not what nobody may see is NaN. Query blocks wholly past the prompt
    come back as zeros."""
    q_len = Q_LENS[ends](windows)
    clean, dirty = rows_of(windows, dtype, q_len)
    want = np.asarray(eva.eva_sequence(*clean, WINDOW, CHUNK), np.float32)
    kernel = functools.partial(
        ts.two_source_attention, window=WINDOW, chunk=CHUNK,
        q_len=jnp.array([q_len], jnp.int32), block_q=BLOCK_Q,
        block_k=BLOCK_K, interpret=True)
    assert ts.two_source_blocks(HEADS, WINDOW, PER, BLOCK_Q, BLOCK_K) == (
        BLOCK_Q, BLOCK_K, PER, HEADS)
    got = kernel(*dirty)
    assert got.shape == clean[0].shape and got.dtype == dtype
    got = np.asarray(got, np.float32)
    assert np.isfinite(got[:, :q_len]).all()
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[:, :q_len], want[:, :q_len], atol=tol,
                               rtol=0)
    np.testing.assert_array_equal(
        got[:, :q_len], np.asarray(kernel(*clean), np.float32)[:, :q_len])
    assert np.all(got[:, -(-q_len // BLOCK_Q) * BLOCK_Q:] == 0.0)


def test_a_batch_row_stops_at_its_own_length_and_all_rows_is_the_default():
    """`q_len` is a length a batch row; without one every row is live."""
    clean, _ = rows_of(3, jnp.float32, 3 * WINDOW)
    both = tuple(jnp.concatenate([t, t[:, ::-1]]) for t in clean)
    want = np.asarray(eva.eva_sequence(*both, WINDOW, CHUNK))
    kernel = functools.partial(ts.two_source_attention, window=WINDOW,
                               chunk=CHUNK, block_q=BLOCK_Q, block_k=BLOCK_K,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(kernel(*both)), want, atol=1e-4)
    got = np.asarray(kernel(*both, q_len=jnp.array([300, 70], jnp.int32)))
    np.testing.assert_allclose(got[0, :300], want[0, :300], atol=1e-4)
    np.testing.assert_allclose(got[1, :70], want[1, :70], atol=1e-4)
    assert np.all(got[0, 320:] == 0.0) and np.all(got[1, 128:] == 0.0)


def test_the_blocks_divide_a_window_and_the_rule_says_who_is_served(
        monkeypatch):
    """EvaByte's 32 heads of 128 in windows of 2048 rows that hold 128
    summaries go 512 queries x 8 heads against 1024 keys or 128 summaries.
    The rule: not asked off the chip; on it, no mesh of several devices in
    context, heads of whole lane tiles, whole windows, and a window that
    holds whole 128-row blocks of summaries."""
    assert ts.two_source_blocks(32, 2048, 128) == (512, 1024, 128, 8)
    assert ts.two_source_blocks(4, 2048, 256) == (512, 1024, 256, 4)
    assert ts.two_source_blocks(32, 2048, 96) is None
    q = (1, 6144, 32, 128)
    assert ts.two_source_refusal(q, 2048, 16) == attention.NOT_ASKED
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    assert ts.two_source_refusal(q, 2048, 16) is None
    assert ts.two_source_refusal((1, 512, HEADS, DIM), WINDOW, CHUNK) is None
    assert "lane tiles" in ts.two_source_refusal((1, 6144, 32, 96), 2048, 16)
    assert "whole number of windows" in ts.two_source_refusal(
        (1, 5000, 32, 128), 2048, 16)
    assert "128-row blocks" in ts.two_source_refusal((1, 64, 4, 128), 32, 4)
    assert "128-row blocks" in ts.two_source_refusal(q, 2048, 24)
    monkeypatch.setattr(attention, "mesh_refusal", lambda: "a mesh")
    assert ts.two_source_refusal(q, 2048, 16) == "a mesh"
    rows = lambda n: jnp.zeros((1, n, 4, 128))  # noqa: E731
    with pytest.raises(ValueError, match="two_source_refusal"):
        ts.two_source_attention(rows(64), rows(64), rows(64), rows(16),
                                rows(16), window=32, chunk=4, interpret=True)


def as_the_chip(monkeypatch) -> list:
    """The dispatchers' question about the backend answered as the chip
    would, the two-source kernel in interpret mode, the flash kernel and
    the decode step's kept on their XLA forms; the kernel's calls."""
    calls = []
    kernel = functools.partial(ts.two_source_attention, interpret=True)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "kernel_refusal",
                        lambda *shapes, **kw: attention.NOT_ASKED)
    monkeypatch.setattr(da, "two_leaf_refusal",
                        lambda *shapes: attention.NOT_ASKED)
    monkeypatch.setattr(
        eva, "two_source_attention",
        lambda *rows, **kw: calls.append(kw) or kernel(*rows, **kw))
    return calls


def test_the_dispatcher_takes_the_kernel_on_the_chip_and_the_scan_under_grad(
        monkeypatch):
    """`eva_attention`: the tile scan off the chip; on it the kernel,
    handed the prompt's length, and under `jax.grad` the tile scan for the
    forward and the backward pass (the kernel has no VJP)."""
    clean, _ = rows_of(2, jnp.float32, 2 * WINDOW)
    q_len = jnp.array([300], jnp.int32)
    want = np.asarray(eva.eva_sequence(*clean, WINDOW, CHUNK))
    calls = as_the_chip(monkeypatch)
    monkeypatch.setattr(attention, "on_tpu", lambda: False)
    np.testing.assert_array_equal(
        np.asarray(eva.eva_attention(*clean, WINDOW, q_len)), want)
    assert not calls
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    got = np.asarray(eva.eva_attention(*clean, WINDOW, q_len))
    assert len(calls) == 1 and calls[0]["q_len"] is q_len
    np.testing.assert_allclose(got[:, :300], want[:, :300], atol=1e-4)
    loss = lambda form, *at: lambda *rows: jnp.sum(  # noqa: E731
        form(*rows, *at) ** 2)
    grads = jax.grad(loss(eva.eva_attention, WINDOW),
                     argnums=(0, 1, 2, 3, 4))(*clean)
    assert len(calls) == 1
    for got, want in zip(grads, jax.grad(
            loss(eva.eva_sequence, WINDOW, CHUNK),
            argnums=(0, 1, 2, 3, 4))(*clean)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


# ------------------------------------------------------------- the engine
#: `tests/test_two_leaf_decode.py`'s toy with a window that holds a block's
#: worth of summaries: 2 layers, 2 heads of 128, a window of 256 positions
#: beside a summary for every chunk of 2, 1024 positions a slot.
EVA = LLMConfig(
    vocab_size=64, d_model=256, n_layers=2, n_heads=2, max_seq=1024,
    dtype="float32", seed=0,
    arch={"model_type": "evabyte", "attention_class": "eva",
          "chunk_size": CHUNK, "window_size": WINDOW, "num_chunks": None,
          "num_key_value_heads": 2, "intermediate_size": 96,
          "hidden_act": "silu", "attention_bias": False, "rope_theta": 100000,
          "rope_scaling": None, "rms_norm_eps": 1e-5,
          "norm_add_unit_offset": True, "fp32_skip_add": True,
          "fp32_logits": True, "num_pred_heads": 8,
          "tie_word_embeddings": False, "pool_init_std": 4.0})
#: under a window (the flash kernel's bucket), two windows, three of four
PROMPTS = (200, 300, 700)


def run():
    eng = ContinuousEngine(EVA, max_batch=2, decode_chunk=4)
    try:
        rng = np.random.default_rng(51)
        toks = [eng.submit(rng.integers(1, 64, size=n).tolist(),
                           SamplingParams(temperature=0.0, max_tokens=6)
                           ).tokens() for n in PROMPTS]
        return toks, eng.cache_stats(), {
            b: eng._prefill_form(b) for b in (256, 512, 1024)}
    finally:
        eng.shutdown()


def test_the_engine_serves_the_same_tokens_from_a_prefill_through_the_kernel(
        monkeypatch):
    """An "eva" engine on the chip's answers: every bucket past a window is
    prefilled through the kernel, ONE call a layer whatever the bucket, the
    prompt's length with it; `prefill_rows_kernel` counts those buckets'
    rows; and the tokens are the ones the tile scan's prefill leads to."""
    want, stats, forms = run()
    assert set(forms.values()) == {"xla"}
    assert (stats["prefill_rows"], stats["prefill_rows_kernel"]) == (1792, 0)
    calls = as_the_chip(monkeypatch)
    got, stats, forms = run()
    assert got == want
    # (the flash kernel's bucket is held to its XLA form here)
    assert forms == {256: "xla", 512: "kernel", 1024: "kernel"}
    assert (stats["prefill_rows"], stats["prefill_rows_kernel"]) == (
        1792, 1536)
    assert len(calls) == 2 * EVA.n_layers  # traced once a bucket a layer
    assert all(kw["q_len"] is not None for kw in calls)
