"""The ragged two-leaf decode attention (README "Serving hot loop";
`ops/decode_attention.py` `ragged_two_leaf_attention`): ONE Pallas kernel
for an "eva" layer's decode step, which stops every slot at its own row in
BOTH leaves, the window's rows and the summaries behind them, under one
softmax, and reads nothing of a free slot.

Here, on the CPU in interpret mode (tests/test_ragged_decode.py's way): the
kernel against the XLA form it replaces on the chip (`partial_walk` x 2 +
`merge_partials`) and against a dense softmax over the concatenated visible
rows, at the stops where a block begins and ends and a window starts over,
with free slots, and with everything no slot may see poisoned; the
dispatcher's rule; and the engine serving the same tokens through the kernel
as through the walks. What the TPU's compiler makes of it is
tests/test_v5e_compile.py's."""

import functools
import importlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import tracing
from ray_tpu.llm import LLMConfig
from ray_tpu.llm.engine import ContinuousEngine, SamplingParams
from ray_tpu.ops import attention

da = importlib.import_module("ray_tpu.ops.decode_attention")

#: A window of 64 rows beside 64 summaries of 4 positions each (256
#: positions a slot), in blocks of 16 rows: a window holds 16 summaries, so
#: a summaries' stop is whole blocks, as EvaByte's 128 of a window of 2048.
WINDOW, SUMMARIES, BLOCK, PER = 64, 64, 16, 16
HEADS, DIM = 4, 128


def at_positions(*positions):
    """(stop_w, stop_c) of live slots at these positions, as
    `models/eva.py` `_step` hands them over; None is a free slot."""
    return ([0 if p is None else p % WINDOW + 1 for p in positions],
            [0 if p is None else p // WINDOW * PER for p in positions])


#: name -> (stop_w, stop_c) a slot
CASES = {
    "sixteen_slots_at_scattered_phases": at_positions(
        *np.random.default_rng(50).integers(0, 4 * WINDOW, 16).tolist()),
    "the_first_window_has_no_summary": at_positions(0, 15, 16, 40, 63),
    "a_window_that_just_started_over": at_positions(
        WINDOW, 2 * WINDOW, 3 * WINDOW),
    "a_blocks_last_row_one_past_it_and_the_whole_window": at_positions(
        BLOCK - 1, WINDOW + BLOCK, 2 * WINDOW + WINDOW - 1, 3 * BLOCK - 1),
    "a_free_slot_between_live_ones": at_positions(
        70, None, 3 * WINDOW + 20, None, 5),
    "free_slots_first_and_last": at_positions(None, 130, 64, None),
    "all_slots_free": at_positions(None, None, None),
    # (no "eva" layer asks this: the kernel masks inside a summaries' block
    # as inside a window's)
    "summaries_that_stop_inside_a_block": ([7, 64, 1, 33], [5, 20, 64, 0]),
}


def leaves_of(slots: int, dtype, stops):
    """q, (K, V) of the window, (Kbar, Vbar) of the summaries, and the same
    leaves with everything no slot may see, every row at and above a stop
    (all of a free slot's among them), poisoned with NaN: in the blocks
    that hold no visible row the kernel must not fetch it into its sums,
    and inside a stop's own block it must keep it out of them (a masked
    score, and a value zeroed before its product: 0 x NaN is NaN)."""
    keys = jax.random.split(jax.random.PRNGKey(slots), 5)
    q = jax.random.normal(keys[0], (slots, HEADS, DIM), dtype)
    clean = [jax.random.normal(key, (slots, rows, HEADS, DIM), dtype)
             for key, rows in zip(keys[1:], (WINDOW, WINDOW, SUMMARIES,
                                             SUMMARIES))]
    dirty = []
    for leaf, stop in zip(clean, (stops[0], stops[0], stops[1], stops[1])):
        stop = np.asarray(stop)[:, None, None, None]
        row = np.arange(leaf.shape[1])[None, :, None, None]
        dirty.append(jnp.where(row >= stop, jnp.nan, leaf))
    return q, clean, dirty


def dense(q, leaves, stop):
    """The softmax over rows `[0, stop_w)` of the window and `[0, stop_c)`
    of the summaries, written out slot by slot in float64."""
    q, (kw, vw, kc, vc) = np.asarray(q, np.float64), (
        np.asarray(leaf, np.float64) for leaf in leaves)
    out = np.zeros(q.shape)
    for i, (w, c) in enumerate(zip(*stop)):
        if w + c:
            keys = np.concatenate([kw[i, :w], kc[i, :c]])
            s = np.einsum("hd,thd->ht", q[i], keys) / DIM ** 0.5
            p = np.exp(s - s.max(-1, keepdims=True))
            out[i] = np.einsum("ht,thd->hd", p / p.sum(-1, keepdims=True),
                               np.concatenate([vw[i, :w], vc[i, :c]]))
    return out


@pytest.mark.parametrize("against", ["the_two_walks_merged",
                                     "a_dense_softmax"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_stops_every_slot_at_its_own_row_in_both_leaves(
        case, dtype, against, monkeypatch):
    """The kernel on the poisoned leaves against the XLA form or the dense
    softmax on the clean ones: what lies above a slot's stop, in its block
    or beyond it, and all of a free slot's rows, leave every output as it
    was; a free slot's own output, which nobody reads, is zeros."""
    stops = CASES[case]
    slots = len(stops[0])
    q, clean, dirty = leaves_of(slots, dtype, stops)
    monkeypatch.setattr(
        da, "BLOCK_BYTES", BLOCK * HEADS * DIM * jnp.dtype(dtype).itemsize)
    assert da.two_leaf_block(clean[0].shape, clean[2].shape, dtype) == BLOCK
    stop_w, stop_c = (jnp.asarray(stop, jnp.int32) for stop in stops)
    got = da.ragged_two_leaf_attention(q, dirty[:2], dirty[2:], stop_w,
                                       stop_c, interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    got = np.asarray(got, np.float32)
    free = [i for i in range(slots) if not stops[0][i] + stops[1][i]]
    assert np.all(got[free] == 0.0)
    if against == "a_dense_softmax":
        want = dense(q, clean, stops)
    else:
        want = np.asarray(da.merge_partials(
            da.partial_walk(q, *clean[:2], stop_w, jnp.max(stop_w)),
            da.partial_walk(q, *clean[2:], stop_c, jnp.max(stop_c))))
    # bf16: the output's own rounding; the sums are float32 in both forms
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=0)


def test_the_block_divides_both_leaves_and_the_rule_says_who_is_served(
        monkeypatch):
    """EvaByte's leaves go 128 rows a block, a MiB of K, in both leaves.
    The rule: not asked off the chip; on it, heads of their own in rows of
    whole lane tiles and no mesh of several devices in context."""
    window, summaries = (16, 2048, 32, 128), (16, 1024, 32, 128)
    q = (16, 32, 128)
    assert da.two_leaf_block(window, summaries, jnp.bfloat16) == 128
    assert da.two_leaf_block((2, 64, 4, 128), (2, 48, 4, 128),
                             jnp.float32) == 16
    assert da.two_leaf_block((2, 64, 4, 128), (2, 36, 4, 128),
                             jnp.float32) is None
    assert da.two_leaf_refusal(q, window, summaries) == attention.NOT_ASKED
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    assert da.two_leaf_refusal(q, window, summaries) is None
    assert "lane tiles" in da.two_leaf_refusal(
        (16, 32, 96), (16, 2048, 32, 96), (16, 1024, 32, 96))
    assert "heads" in da.two_leaf_refusal(q, (16, 2048, 4, 128),
                                          (16, 1024, 4, 128))
    assert "share no block" in da.two_leaf_refusal(
        (2, 4, 128), (2, 64, 4, 128), (2, 36, 4, 128), jnp.float32)
    monkeypatch.setattr(attention, "mesh_refusal", lambda: "a mesh")
    assert da.two_leaf_refusal(q, window, summaries) == "a mesh"
    with pytest.raises(ValueError, match="two_leaf_refusal"):
        da.ragged_two_leaf_attention(
            jnp.zeros((2, 4, 128)), [jnp.zeros((2, 64, 4, 128))] * 2,
            [jnp.zeros((2, 36, 4, 128))] * 2, jnp.zeros(2, jnp.int32),
            jnp.zeros(2, jnp.int32), interpret=True)


def test_the_dispatcher_takes_the_kernel_for_a_bounded_step_on_the_chip(
        monkeypatch):
    """`two_leaf_decode_attention`: the walks off the chip and for the
    unbounded step; on the chip a bounded step is the kernel's."""
    stops = CASES["sixteen_slots_at_scattered_phases"]
    q, clean, _ = leaves_of(16, jnp.float32, stops)
    stop_w, stop_c = (jnp.asarray(stop, jnp.int32) for stop in stops)
    calls = []
    monkeypatch.setattr(da, "BLOCK_BYTES", BLOCK * HEADS * DIM * 4)
    kernel = functools.partial(da.ragged_two_leaf_attention, interpret=True)
    monkeypatch.setattr(da, "ragged_two_leaf_attention",
                        lambda *a: calls.append(a) or kernel(*a))
    ask = lambda bounded: np.asarray(da.two_leaf_decode_attention(  # noqa: E731
        q, clean[:2], clean[2:], stop_w, stop_c, bounded=bounded))
    walked = ask(True)
    assert not calls
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    np.testing.assert_allclose(ask(False), walked, atol=1e-5)
    assert not calls
    np.testing.assert_allclose(ask(True), walked, atol=1e-4)
    assert len(calls) == 1


# ------------------------------------------------------------- the engine
#: `tests/test_eva.py`'s toy with heads of 128: 2 layers, a window of 32
#: positions that starts over beside a summary for every chunk of 4, 128
#: positions a slot, in blocks of 8 rows: a window holds 8 summaries.
EVA_WINDOW, EVA_CHUNK, EVA_BLOCK = 32, 4, 8
EVA = LLMConfig(
    vocab_size=64, d_model=256, n_layers=2, n_heads=2, max_seq=128,
    dtype="float32", seed=0,
    arch={"model_type": "evabyte", "attention_class": "eva",
          "chunk_size": EVA_CHUNK, "window_size": EVA_WINDOW,
          "num_chunks": None, "num_key_value_heads": 2,
          "intermediate_size": 96, "hidden_act": "silu",
          "attention_bias": False, "rope_theta": 100000,
          "rope_scaling": None, "rms_norm_eps": 1e-5,
          "norm_add_unit_offset": True, "fp32_skip_add": True,
          "fp32_logits": True, "num_pred_heads": 8,
          "tie_word_embeddings": False, "pool_init_std": 4.0})


def on_the_chip(monkeypatch) -> None:
    """tests/test_ragged_decode.py's `on_the_chip` for an "eva" model: the
    backend is a TPU, the two-leaf kernel runs in interpret mode in blocks
    of `EVA_BLOCK` rows, and the prefill keeps its XLA form."""
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "kernel_refusal",
                        lambda *shapes, **kw: attention.NOT_ASKED)
    monkeypatch.setattr(da, "ragged_two_leaf_attention", functools.partial(
        da.ragged_two_leaf_attention, interpret=True))
    monkeypatch.setattr(da, "BLOCK_BYTES", EVA_BLOCK * 2 * 128 * 4)


def run(prompts, budgets, mesh=None):
    eng = ContinuousEngine(EVA, max_batch=3, decode_chunk=4, mesh=mesh)
    try:
        streams = [eng.submit(p, SamplingParams(max_tokens=m,
                                                temperature=0.0))
                   for p, m in zip(prompts, budgets)]
        return ([s.tokens() for s in streams],
                {**eng.cache_stats(), "attention": eng._decode_form,
                 "blocks": eng._kernel_blocks})
    finally:
        eng.shutdown()


#: Ten requests on three slots: rows change hands, slots stand free,
#: windows start over during decode, prompts end in the first window and
#: two windows in.
PROMPTS = [np.random.default_rng(3).integers(1, 64, size=n).tolist()
           for n in (5, 40, 17, 70, 31, 3, 33, 64, 21, 90)]
BUDGETS = [40, 30, 20, 50, 5, 16, 36, 3, 11, 30]


def test_the_engine_serves_the_same_tokens_through_the_two_leaf_kernel(
        monkeypatch):
    """The tokens with the kernel forced, in interpret mode, are the two
    walks'; every decode step is counted under the kernel, and each leaf's
    walked share is the live slots' own rows rounded up to the block, under
    a block more than they show, where the walks' is their quarter
    prefixes'."""
    want, xla = run(PROMPTS, BUDGETS)
    assert (xla["attention"], xla["blocks"]) == ("xla", {})
    assert xla["decode_steps"] > 0 and xla["decode_steps_kernel"] == 0
    on_the_chip(monkeypatch)
    got, kernel = run(PROMPTS, BUDGETS)
    assert got == want
    assert (kernel["attention"], kernel["blocks"]) == (
        "kernel", {"window": EVA_BLOCK, "chunks": EVA_BLOCK})
    assert kernel["decode_steps_kernel"] == kernel["decode_steps"] > 0
    kinds = kernel["cache_kinds"]
    assert kinds["window"]["live_share"] < kinds["window"]["walk_share"] < (
        kinds["window"]["live_share"] + EVA_BLOCK / EVA_WINDOW)
    # a window holds a block of summaries: read with no rounding at all
    assert kinds["chunks"]["walk_share"] == pytest.approx(
        kinds["chunks"]["live_share"])
    assert kinds["chunks"]["walk_share"] < xla["cache_kinds"]["chunks"][
        "walk_share"]


def test_an_eva_engine_given_a_tp_mesh_keeps_the_walks(monkeypatch):
    """With a `tp` mesh in context the rule the counters asked is the rule
    the trace asks: both keep the two walks over leaves sharded on the head
    axis, and the kernel is never called."""
    from jax.sharding import Mesh

    want, _ = run(PROMPTS[:3], BUDGETS[:3])
    on_the_chip(monkeypatch)

    def never(*args, **kwargs):
        raise AssertionError("the two-leaf kernel under a `tp` mesh")

    monkeypatch.setattr(da, "ragged_two_leaf_attention", never)
    got, sharded = run(PROMPTS[:3], BUDGETS[:3],
                       mesh=Mesh(np.array(jax.devices()[:2]), ("tp",)))
    assert got == want
    assert (sharded["attention"], sharded["blocks"]) == ("xla", {})
    assert sharded["decode_steps"] > 0 == sharded["decode_steps_kernel"]


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


def test_a_chunks_span_says_kernel_and_carries_both_leaves_rows_in_blocks(
        spans, monkeypatch):
    """tests/test_eva.py's traced request (a prompt of 26, 44 tokens: the
    window starts over at 32 and 64) with the kernel serving: the chunk's
    span says `attention: kernel`, `kv_rows_window` is the slot's own rows
    rounded up to the block, a whole multiple of it, and `kv_rows_chunks`
    the summaries it sees, none in the first window (the walk read a
    quarter there) and a window's worth, whole blocks, for each window
    behind it."""
    on_the_chip(monkeypatch)
    eng = ContinuousEngine(EVA, max_batch=3, decode_chunk=4)
    try:
        tracing._ctx.set(("5" * 32, "6" * 16))
        stream = eng.submit(PROMPTS[1][:26], SamplingParams(
            temperature=0.7, top_k=8, max_tokens=44))
        tracing._ctx.set(None)
        assert len(stream.tokens()) == 44
        deadline = time.monotonic() + 120
        while eng.num_active and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        eng.shutdown()
    chunks = [s["at"] for s in spans if s["n"] == "engine.dispatch_chunk"]
    assert chunks and all(at["attention"] == "kernel" for at in chunks)
    per = EVA_WINDOW // EVA_CHUNK
    for at in chunks:
        bound, n = at["kv_bound"], at["tokens"]
        pos = np.arange(bound - n, bound)  # the positions stepped
        shown_w, shown_c = pos % EVA_WINDOW + 1, pos // EVA_WINDOW * per
        assert at["kv_live_window"] == pytest.approx(shown_w.mean(), abs=.01)
        assert at["kv_live_chunks"] == pytest.approx(shown_c.mean(), abs=.01)
        # (a step's mean over the chunk, said in whole blocks)
        for kind, shown in (("window", shown_w), ("chunks", shown_c)):
            blocks = np.ceil(shown / EVA_BLOCK).mean()
            assert at["kv_rows_" + kind] == int(round(blocks)) * EVA_BLOCK
        assert at["kv_rows"] == at["kv_rows_window"]
    assert any(at["kv_rows_chunks"] == 0 for at in chunks)
    assert any(at["kv_rows_chunks"] == 2 * per for at in chunks)
