"""Production LLM serving: continuous batching engine + OpenAI surface.

Parity target: reference python/ray/llm/_internal/serve — vLLM engine seat
(continuous batching, sampling, streaming) + OpenAI-compatible router
(routers/router.py) + build_openai_app (application_builders.py).
"""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu.llm import LLMConfig
from ray_tpu.llm.engine import ContinuousEngine, SamplingParams

CFG = LLMConfig(vocab_size=384, d_model=64, n_layers=2, n_heads=4,
                max_seq=128)


@pytest.fixture(scope="module")
def engine():
    eng = ContinuousEngine(CFG, max_batch=4, decode_chunk=4)
    yield eng
    eng.shutdown()


def test_engine_greedy_deterministic(engine):
    a = engine.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                max_tokens=6)).tokens()
    b = engine.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                max_tokens=6)).tokens()
    assert a == b and len(a) == 6


def test_engine_no_lockstep(engine):
    """Requests of different lengths complete independently — the defining
    property of continuous batching vs whole-batch generate()."""
    # 120 tokens (30 decode chunks): wide enough that the consumer thread
    # reliably observes the long request still active right after the short
    # one drains, even when a loaded CI box deschedules it for a while.
    # Anchor the short submit on the long request's FIRST token rather
    # than a wall-clock sleep: the pipelined hot loop decodes the whole
    # 120 fast enough that a fixed sleep could eat its entire lifetime.
    long_s = engine.submit([5, 6, 7], SamplingParams(temperature=0.0,
                                                     max_tokens=120))
    first_long = long_s.next(timeout=60)
    t0 = time.monotonic()
    short = engine.submit([8, 9], SamplingParams(temperature=0.0,
                                                 max_tokens=3)).tokens()
    short_done = time.monotonic() - t0
    # the long request must still be in flight when the short one finished
    assert engine.num_active >= 1
    assert len(short) == 3
    long_toks = [first_long] + long_s.tokens()
    assert len(long_toks) == 120
    assert short_done < 30.0


def test_engine_join_running_batch(engine):
    """A request submitted mid-decode joins the running batch (its first
    token arrives long before the in-flight request finishes)."""
    long_s = engine.submit([1], SamplingParams(temperature=0.0,
                                               max_tokens=80))
    # wait until the long request has produced a few tokens
    first_long = long_s.next(timeout=60)
    joiner = engine.submit([2, 3], SamplingParams(temperature=0.0,
                                                  max_tokens=4))
    first_join = joiner.next(timeout=60)
    assert isinstance(first_long, int) and isinstance(first_join, int)
    # long request still active after the joiner got its first token
    assert engine.num_active >= 1
    joiner.tokens()
    long_s.tokens()


def test_engine_sampling_modes(engine):
    greedy = engine.submit([1, 2, 3], SamplingParams(
        temperature=0.0, max_tokens=8)).tokens()
    topk1 = engine.submit([1, 2, 3], SamplingParams(
        temperature=1.0, top_k=1, max_tokens=8)).tokens()
    assert topk1 == greedy  # top_k=1 collapses to greedy
    hot1 = engine.submit([1, 2, 3], SamplingParams(
        temperature=8.0, max_tokens=16, seed=11)).tokens()
    hot2 = engine.submit([1, 2, 3], SamplingParams(
        temperature=8.0, max_tokens=16, seed=22)).tokens()
    assert hot1 != hot2  # high temperature + different seeds diverge
    capped = engine.submit([1, 2, 3], SamplingParams(
        temperature=8.0, top_p=1e-9, max_tokens=8, seed=5)).tokens()
    assert capped == greedy  # tiny top_p keeps only the argmax token


def test_engine_stop_token(engine):
    base = engine.submit([4, 5], SamplingParams(
        temperature=0.0, max_tokens=12)).tokens()
    stop = base[3]
    s = engine.submit([4, 5], SamplingParams(
        temperature=0.0, max_tokens=12, stop_token=int(stop)))
    toks = s.tokens()
    assert toks[-1] == stop and len(toks) == 4
    assert s.finish_reason == "stop"


def test_engine_overflow_rejected(engine):
    with pytest.raises(ValueError, match="max_seq"):
        engine.submit(list(range(100)), SamplingParams(max_tokens=100))


def test_serve_openai_http(ray_start_4cpu):
    """End-to-end: OpenAI app over HTTP — models list, completion, and SSE
    token streaming (tokens must ARRIVE incrementally)."""
    from ray_tpu import serve
    from ray_tpu.llm.openai import build_openai_app

    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    app = build_openai_app(CFG, model_id="test-llm", max_batch=4,
                           decode_chunk=4, default_max_tokens=8)
    serve.run(app, route_prefix="/", port=port)
    try:
        base = f"http://127.0.0.1:{port}"
        # /v1/models
        with urllib.request.urlopen(f"{base}/v1/models", timeout=30) as r:
            models = json.loads(r.read())
        assert models["data"][0]["id"] == "test-llm"
        # non-streaming completion
        body = json.dumps({"prompt": "hi", "max_tokens": 5,
                           "temperature": 0.0}).encode()
        req = urllib.request.Request(
            f"{base}/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            out = json.loads(r.read())
        assert out["object"] == "text_completion"
        assert len(out["token_ids"]) == 5
        assert out["choices"][0]["finish_reason"] == "length"
        # streaming completion (SSE)
        body = json.dumps({"prompt": "hi", "max_tokens": 6,
                           "temperature": 0.0, "stream": True}).encode()
        req = urllib.request.Request(
            f"{base}/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        chunks, arrival = [], []
        with urllib.request.urlopen(req, timeout=120) as r:
            for line in r:
                line = line.decode().strip()
                if not line.startswith("data: "):
                    continue
                payload = line[len("data: "):]
                arrival.append(time.monotonic())
                if payload == "[DONE]":
                    chunks.append(None)
                    break
                chunks.append(json.loads(payload))
        assert chunks[-1] is None  # [DONE] terminator
        deltas = [c for c in chunks[:-1] if c]
        # 6 token chunks + 1 finish chunk
        toks = [t for c in deltas for t in c.get("token_ids", [])]
        assert len(toks) == 6
        assert deltas[-1]["choices"][0]["finish_reason"] == "length"
        # chat form
        body = json.dumps({"messages": [{"role": "user", "content": "yo"}],
                           "max_tokens": 4, "temperature": 0.0}).encode()
        req = urllib.request.Request(
            f"{base}/v1/chat/completions", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            out = json.loads(r.read())
        assert out["object"] == "chat.completion"
        assert out["choices"][0]["message"]["role"] == "assistant"
        # /v1/stats: the replica says, from inside its own process, which
        # device it runs on and how many requests it has taken.
        with urllib.request.urlopen(f"{base}/v1/stats", timeout=30) as r:
            st = json.loads(r.read())
        assert st["pid"] != os.getpid()
        assert st["platform"] == "cpu" and st["device_kind"]
        assert st["device_ids"] and st["tpu_visible_chips"] is None
        assert st["chip_files_open"] == []
        assert st["served"] == 3 and st["active"] == 0
        assert st["compile_count"] > 0 and st["engine_init_s"] > 0
        # ... and the layout its chunk program takes the KV cache in (on
        # the CPU the default one, so nothing is converted); the CPU keeps
        # no count of device memory.
        assert "major_to_minor" in st["cache_layout"]
        assert st["cache_boundary_copies"] == 0
        assert st["memory_peak_bytes"] is None
        # ... and how its decode steps sampled: every request here is
        # greedy, so none went through the sampled program.
        assert st["sampler_steps"] == st["sampler_steps_select"] == 0
    finally:
        serve.shutdown()


def test_serve_handle_streaming(ray_start_2cpu):
    """Python-side handle streaming: handle.options(stream=True) yields
    refs incrementally from a generator deployment method."""
    from ray_tpu import serve

    @serve.deployment
    class Counter:
        def counted(self, n):
            for i in range(n):
                yield {"i": i}

    serve.run(Counter.bind(), route_prefix="/counter")
    try:
        h = serve.get_deployment_handle("Counter")
        gen = h.counted.options(stream=True).remote(5)
        vals = [ray_tpu.get(ref)["i"] for ref in gen]
        assert vals == [0, 1, 2, 3, 4]
    finally:
        serve.shutdown()


# ---------------------------------------------------------------------------
# The KV cache crosses program boundaries in the layout the decode loop
# computes in: the engine asks the compiler for it and, where a wider row
# makes it the default layout, widens the cache's rows (README "Serving hot
# loop"). On the CPU the compiler's answer is the default layout.


def test_cache_stats_name_the_layout_and_count_boundary_copies(engine):
    st = engine.cache_stats()
    assert set(st) == {"cache_layout", "cache_boundary_copies",
                       "cache_kind", "cache_bytes", "kv_walk_share",
                       "kv_live_share", "splices", "splices_in_flight",
                       "pipeline_dry", "cover_chunks", "cache_kinds", "kv_heads",
                       "prefill_rows", "prefill_rows_kernel",
                       "sampler_steps", "sampler_steps_select",
                       "decode_steps", "decode_steps_kernel"}
    assert 0.0 <= st["kv_live_share"] <= st["kv_walk_share"] <= 1.0
    # one kind of leaf: every layer keeps max_seq rows a slot
    assert st["kv_heads"] == CFG.n_heads and st["cache_kinds"] == {"full": {
        "layers": CFG.n_layers, "leaves": 2 * CFG.n_layers,  # K and V
        "rows": CFG.max_seq,
        "bytes": st["cache_bytes"], "walk_share": st["kv_walk_share"],
        "live_share": st["kv_live_share"]}}
    assert st["cache_kind"] == "kv"
    assert st["cache_bytes"] == (CFG.n_layers * 2 * 4 * CFG.max_seq
                                 * CFG.d_model * 4)
    head_dim = CFG.d_model // CFG.n_heads
    assert st["cache_layout"].startswith(
        f"{CFG.dtype}[4, {CFG.max_seq}, {CFG.n_heads}, {head_dim}] Layout(")
    # The CPU's own choice is the default layout: nothing to convert, and
    # the rows stay as wide as a head.
    assert st["cache_boundary_copies"] == 0
    assert engine.model.cfg.cache_row == 0


def test_cache_stats_of_a_layer_with_two_latents_count_leaves_beside_layers():
    """What `/v1/stats` reports for a model whose layer holds two latent
    attentions and an expert layer with identity experts (`model_type`
    `longcat_flash`, four layers as the benchmark's cut): `leaves` 8 beside
    `layers` 4 with `bytes` over all 8, the identity experts, the router's
    width, and the selections counted since start."""
    arch = {"model_type": "longcat_flash", "attention_method": "MLA",
            "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16,
            "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
            "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32,
            "n_routed_experts": 8, "zero_expert_num": 4,
            "zero_expert_type": "identity", "moe_topk": 3,
            "routed_scaling_factor": 6, "rope_theta": 1e7,
            "rms_norm_eps": 1e-5}
    eng = ContinuousEngine(LLMConfig(
        vocab_size=64, d_model=32, n_layers=4, n_heads=2, max_seq=32,
        dtype="float32", arch=arch, experts_held=2), max_batch=2,
        decode_chunk=4)
    try:
        out = eng.submit([5, 6, 7], SamplingParams(temperature=0.0,
                                                   max_tokens=6)).tokens()
        assert len(out) == 6
        st = eng.cache_stats()
        full = st["cache_kinds"]["full"]
        assert (full["layers"], full["leaves"], full["rows"]) == (4, 8, 32)
        assert full["bytes"] == st["cache_bytes"] == 8 * 2 * 32 * 24 * 4
        assert (st["zero_experts"], st["router_outputs"],
                st["experts_held"], st["experts_published"]) == (4, 12, 2, 8)
        steps = st["decode_steps"]
        # every slot of the batch x 3 selections x 4 expert layers a step
        assert st["moe_picks_total"] == steps * 2 * 3 * 4
        assert 0 <= st["moe_zero_picks_total"] <= st["moe_picks_total"]
        assert st["moe_rows_total"] <= (st["moe_picks_total"]
                                        - st["moe_zero_picks_total"])
        # 2 held experts x 4 expert layers a step read (the dense arm),
        # of which some got a row
        assert st["moe_touched_total"] <= st["moe_fetched_total"] == steps * 8
    finally:
        eng.shutdown()


@pytest.mark.parametrize("row", [32, 128])
def test_widened_cache_rows_decode_the_same_tokens(engine, monkeypatch, row):
    """An engine whose compiler asks for wider cache rows (as the v5e's
    does for heads of 96) serves the same greedy tokens, token for token,
    through zero cache, splices and chunks of four lengths, and hands the
    cache from program to program at that width."""
    import jax

    monkeypatch.setattr(ContinuousEngine, "_probe_cache_row",
                        lambda self, make_chunk: row)
    wide = ContinuousEngine(CFG, max_batch=4, decode_chunk=4)
    try:
        assert wide.model.cfg.cache_row == row
        assert f", {row}] Layout(" in wide.cache_stats()["cache_layout"]
        # One at a time: alone in the batch a request decodes through a
        # fixed sequence of chunk programs (12 tokens = 1 + 4 + 4 + 2 + 1).
        sp = SamplingParams(temperature=0.0, max_tokens=12)
        for prompt in ([1, 2, 3], [7, 5, 3, 2, 1, 8, 9, 4, 6], [11]):
            want = engine.submit(prompt, sp).tokens()
            assert wide.submit(prompt, sp).tokens() == want
        # ... and a request that joins a running batch (a splice beside
        # live rows), sampled.
        a = wide.submit([3, 1, 4, 1, 5], SamplingParams(temperature=0.0,
                                                        max_tokens=9))
        a.next(timeout=60)
        b = wide.submit([9, 2, 6], SamplingParams(temperature=0.7, top_k=5,
                                                  max_tokens=6))
        assert len(b.tokens()) == 6 and len(a.tokens()) == 8
        leaves = jax.tree.leaves(wide._cache)
        assert len(leaves) == 2 * CFG.n_layers
        for leaf in leaves:
            assert leaf.shape == (4, CFG.max_seq, CFG.n_heads, row)
    finally:
        wide.shutdown()


def test_rows_an_earlier_request_left_in_a_slot_are_never_read():
    """A splice writes the rows of the prompt's bucket and no more; what a
    longer request left further down the slot stays there and must stay
    invisible: a short prompt decodes through those rows exactly as it does
    in a slot that has only ever held zeros."""
    sp = SamplingParams(temperature=0.0, max_tokens=40)
    short = [[5, 6, 7], [9], [2, 4, 6, 8, 10]]  # bucket 8, decoded to 40+
    fresh = ContinuousEngine(CFG, max_batch=4, decode_chunk=4)
    try:
        want = [fresh.submit(p, sp).tokens() for p in short]
        # every slot held to position 100 by a long request (bucket 64)
        long_sp = SamplingParams(temperature=0.9, top_k=8, max_tokens=40)
        rng = np.random.default_rng(0)
        for out in fresh.generate(
                [rng.integers(1, CFG.vocab_size, 60) for _ in range(4)],
                long_sp):
            assert len(out) == 40
        got = [fresh.submit(p, sp).tokens() for p in short]
        assert got == want
    finally:
        fresh.shutdown()


# ---------------------------------------------------------------------------
# The prefill lane's edges: what ends a request that never reaches a slot.
# No pytest-timeout is installed: every wait below has a deadline of its own.

WAIT_S = 60.0


def until(cond, what: str):
    deadline = time.monotonic() + WAIT_S
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.005)


def drain(stream) -> list:
    """The stream's tokens, each awaited at most WAIT_S."""
    out = []
    while True:
        try:
            out.append(stream.next(timeout=WAIT_S))
        except StopIteration:
            return out


def test_a_request_cancelled_while_parked_in_ready_takes_no_slot(
        engine, monkeypatch):
    """Its consumer goes away while its prefill waits in `_ready` for a
    slot: the next admission finishes it as cancelled, splices nothing,
    and the slot goes to the request behind it."""
    sp = SamplingParams(temperature=0.0, max_tokens=6)
    spliced = []
    splice = engine._splice

    def spy(slot, plen, sampling, stream, *rest):
        spliced.append(stream)
        return splice(slot, plen, sampling, stream, *rest)

    monkeypatch.setattr(engine, "_splice", spy)
    with monkeypatch.context() as full:
        full.setattr(engine, "_free_slot", lambda: None)
        parked = engine.submit([1, 2, 3], sp)
        behind = engine.submit([1, 2, 3], sp)
        until(lambda: len(engine._ready) == 2, "both prefills to park")
        parked.close()
    assert len(drain(behind)) == 6
    assert drain(parked) == [] and parked.finish_reason == "cancelled"
    assert spliced == [behind]
    until(lambda: engine.num_active == 0, "the slot to be given back")
    assert not engine._ready and parked not in engine._streams


def test_a_prefill_that_raises_ends_its_own_stream_and_no_other(
        engine, monkeypatch):
    """The lane hands the error to the request it belongs to, counts it
    out of `_prefill_inflight`, and goes on with the next request; the
    scheduler never sees the failed one."""
    sp = SamplingParams(temperature=0.0, max_tokens=6)
    want = drain(engine.submit([4, 5, 6], sp))
    prefill, failed = engine._prefill, []

    def once(*args):
        if not failed:
            failed.append(args)
            raise RuntimeError("prefill failed on the device")
        return prefill(*args)

    monkeypatch.setattr(engine, "_prefill", once)
    bad = engine.submit([4, 5, 6], sp)
    good = engine.submit([4, 5, 6], sp)
    with pytest.raises(RuntimeError, match="prefill failed on the device"):
        bad.next(timeout=WAIT_S)
    assert drain(bad) == []  # the error, then the end of the stream
    assert drain(good) == want
    assert len(failed) == 1 and engine._prefill_inflight == 0
    assert bad not in engine._streams
    assert all(t.is_alive() for t in engine._threads)


def test_shutdown_ends_requests_parked_in_ready_and_in_pending():
    """shutdown() while two requests wait in `_ready` for a slot, one is
    inside its prefill and two more are queued behind it in `_pending`:
    every stream ends, both threads are joined, all within a deadline."""
    import threading

    eng = ContinuousEngine(CFG, max_batch=2, decode_chunk=4)
    gate, inside = threading.Event(), threading.Event()
    prefill, calls = eng._prefill, []

    def third_call_waits(*args):
        calls.append(args)
        if len(calls) == 3:
            inside.set()
            assert gate.wait(timeout=WAIT_S)
        return prefill(*args)

    eng._prefill = third_call_waits
    eng._free_slot = lambda: None  # no slot comes free
    sp = SamplingParams(temperature=0.0, max_tokens=6)
    try:
        streams = [eng.submit([1, 2, 3], sp) for _ in range(2)]
        until(lambda: len(eng._ready) == 2, "two prefills to park")
        streams += [eng.submit([1, 2, 3], sp) for _ in range(3)]
        assert inside.wait(timeout=WAIT_S)
        until(lambda: eng._pending.qsize() == 2, "two requests to queue")
        stopper = threading.Thread(target=eng.shutdown, daemon=True)
        t0 = time.monotonic()
        stopper.start()
        until(lambda: not eng._running, "shutdown to begin")
    finally:
        gate.set()
    stopper.join(timeout=WAIT_S)
    assert not stopper.is_alive()
    assert not any(t.is_alive() for t in eng._threads)
    assert [drain(s) for s in streams] == [[]] * 5
    assert time.monotonic() - t0 < WAIT_S
    assert eng._pending.empty() and not eng._streams
    with pytest.raises(RuntimeError, match="shut down"):
        eng.submit([1, 2, 3], sp)


# ---------------------------------------------------------------------------
# How a batch row changes hands (README "Serving hot loop"): through one device
# program, where its occupant's last token is read; a chunk records its
# occupants, so a row given up early is the next request's at once; while a
# known last step is read and a parked request waits for the row, ONE cover
# chunk of one step steps the occupants who go on. Two rows, chunks of four
# steps; every request is parked in `_ready` before a row is given out, so
# that the order of the hand-overs is the order of the submits.

GREEDY = dict(temperature=0.0)


@pytest.fixture(scope="module")
def pair():
    eng = ContinuousEngine(CFG, max_batch=2, decode_chunk=4)
    yield eng
    eng.shutdown()


def alone(eng, prompt, **sampling) -> list:
    return drain(eng.submit(prompt, SamplingParams(**GREEDY, **sampling)))


def parked(eng, monkeypatch, requests) -> list:
    """Submit `requests` [(prompt, SamplingParams)] while no row is given
    out, wait until every prefill is parked in `_ready`, then let go. The
    engine is at rest first: an earlier request's stream ends where its
    last token is read, one pass before the scheduler has read the step
    dispatched beyond it, and a hand-over behind THAT chunk is none the
    test arranged (on a loaded host the scheduler's thread can stand still
    that long)."""
    at_rest(eng)
    with monkeypatch.context() as full:
        full.setattr(eng, "_free_slot", lambda: None)
        streams = [eng.submit(p, sp) for p, sp in requests]
        until(lambda: len(eng._ready) == len(requests),
              "every prefill to park")
    return streams


def hand_overs(eng, monkeypatch) -> dict:
    """Every `_splice` as stream -> (row, chunks in flight behind which its
    program was dispatched, the steps of each, the passes that had begun
    dry by then)."""
    seen, splice = {}, eng._splice

    def spy(slot, plen, sampling, stream, *rest):
        seen[stream] = (slot, len(eng._q_chunks),
                        [n for _toks, _occ, n, *_rest in eng._q_chunks],
                        eng.pipeline_dry)
        return splice(slot, plen, sampling, stream, *rest)

    monkeypatch.setattr(eng, "_splice", spy)
    return seen


def counted(eng, before: dict) -> dict:
    now = eng.cache_stats()
    return {k: now[k] - before[k] for k in (
        "splices", "splices_in_flight", "pipeline_dry", "cover_chunks")}


def at_rest(eng):
    until(lambda: not (eng._q_chunks or eng._pending_firsts
                       or eng.num_active), "the engine to come to rest")


def test_six_requests_through_two_rows_each_get_the_tokens_they_get_alone(
        pair, monkeypatch):
    """Requests of different lengths, so that rows change hands at six
    different chunk boundaries beside a live neighbour: each stream is the
    one its request gets alone, every row was handed on where its
    occupant's last token was read (the pipeline had drained to there but
    for chunks of ONE step: the neighbour's cover, and the step too many of
    an answer so short that it was dispatched whole before its first token
    was read), and the counters say so."""
    lengths = [5, 9, 14, 3, 7, 11]
    prompts = [[1 + i, 2, 3 + i] for i in range(len(lengths))]
    want = [alone(pair, p, max_tokens=n) for p, n in zip(prompts, lengths)]
    assert [len(w) for w in want] == lengths
    before = pair.cache_stats()
    seen = hand_overs(pair, monkeypatch)
    streams = parked(pair, monkeypatch, [
        (p, SamplingParams(**GREEDY, max_tokens=n))
        for p, n in zip(prompts, lengths)])
    assert [drain(s) for s in streams] == want
    assert {s.finish_reason for s in streams} == {"length"}
    until(lambda: pair.num_active == 0, "the rows to be given back")
    # (a request's steps are counted before its first token is: the one
    # step too many may still be in flight when its last token is read)
    behind = [seen[s][1] for s in streams]
    assert behind[:2] == [0, 0] and max(behind) <= 2
    assert {n for s in streams for n in seen[s][2]} == {1}
    assert {seen[s][0] for s in streams} == {0, 1}
    got = counted(pair, before)
    assert got["splices"] == 6
    assert got["splices_in_flight"] == sum(1 for b in behind if b)
    # (a cover where a neighbour went on and somebody was parked: the next
    # pass did not begin dry)
    assert 1 <= got["cover_chunks"] <= 4
    assert 0 <= got["pipeline_dry"] <= 6 - got["cover_chunks"]
    # (nothing stays in flight: the one step too many is read a pass after
    # the last stream ended, which a loaded host can be a while in coming to)
    at_rest(pair)


def test_a_row_given_up_at_a_stop_token_is_the_next_requests_at_once(
        pair, monkeypatch):
    """The stop is read with chunks in flight that still step the row.
    They were recorded for the occupant that stopped: the next one takes
    that very row behind them, with no wait for them to land, and gets its
    own tokens and none of theirs."""
    base = alone(pair, [4, 5], max_tokens=40)
    stop = base[5]
    cut = base.index(stop) + 1
    long_want = alone(pair, [7, 7, 7], max_tokens=90)
    next_want = alone(pair, [9, 8], max_tokens=13)
    before = pair.cache_stats()
    seen = hand_overs(pair, monkeypatch)
    long_s, stopper, nxt = parked(pair, monkeypatch, [
        ([7, 7, 7], SamplingParams(**GREEDY, max_tokens=90)),
        ([4, 5], SamplingParams(**GREEDY, max_tokens=40, stop_token=stop)),
        ([9, 8], SamplingParams(**GREEDY, max_tokens=13))])
    assert drain(stopper) == base[:cut] and stopper.finish_reason == "stop"
    assert drain(nxt) == next_want and nxt.finish_reason == "length"
    assert drain(long_s) == long_want
    assert seen[nxt][0] == seen[stopper][0] != seen[long_s][0]
    assert seen[nxt][1] >= 1, "the hand-over waited for the chunks in flight"
    assert counted(pair, before)["splices_in_flight"] == 1


def test_a_stream_closed_mid_decode_ends_cancelled_and_frees_its_row(
        pair, monkeypatch):
    """The consumer goes away with chunks of its request in flight: the
    stream ends `cancelled` where the next of them is read, the request
    parked behind it takes the row at once, and that one's tokens and the
    neighbour's are whole."""
    gone_want = alone(pair, [3, 1, 4], max_tokens=60)
    next_want = alone(pair, [2, 7, 1, 8], max_tokens=10)
    other_want = alone(pair, [6], max_tokens=70)
    seen = hand_overs(pair, monkeypatch)
    other, gone, nxt = parked(pair, monkeypatch, [
        ([6], SamplingParams(**GREEDY, max_tokens=70)),
        ([3, 1, 4], SamplingParams(**GREEDY, max_tokens=60)),
        ([2, 7, 1, 8], SamplingParams(**GREEDY, max_tokens=10))])
    got = [gone.next(timeout=WAIT_S)]
    gone.close()
    got += drain(gone)
    assert gone.finish_reason == "cancelled"
    assert len(got) < 60 and got == gone_want[:len(got)]
    assert drain(nxt) == next_want and nxt.finish_reason == "length"
    assert drain(other) == other_want
    assert seen[nxt][0] == seen[gone][0] and seen[nxt][1] >= 1
    until(lambda: pair.num_active == 0, "the rows to be given back")
    assert gone not in pair._streams and not any(pair._slots)


def watched(eng, monkeypatch) -> tuple:
    """The scheduler's chunks as they are dispatched and read: `log` holds
    ("chunk", steps, cover, streams stepped, the block) and ("read", the
    block read or None, streams whose first token was read there), and
    `faults` whatever broke a rule of the hand-over, at any chunk:

    - a chunk dispatched while a seated occupant's known last step is in
      flight is a cover: one step, for the occupants who go on and nobody
      else, with a request parked; any other chunk steps everybody seated;
    - no second chunk goes past an end that has not been read;
    - a cover's read brings no first token but its own occupants'."""
    log, faults = [], []
    passed = {}  # a cover's block -> the occupants whose end it went past

    class Chunks(list):
        def append(self, entry):
            toks, occupants, n, cover, _seq = entry
            seated = [s for s in eng._slots if s is not None]
            ended = [s for s in seated if s.remaining - s.in_flight < 1]
            going = [s for s in seated if s not in ended]
            if occupants != going or cover != bool(ended):
                faults.append(("steps the wrong occupants", n, cover))
            if cover and not (n == 1 and eng._ready):
                faults.append(("a cover nobody waits behind", n,
                               len(eng._ready)))
            if cover and any(not e.done for _t, *_r in self
                             for e in passed.get(id(_t), ())):
                faults.append(("two chunks past one unread end", n))
            if cover:
                passed[id(toks)] = ended
            log.append(("chunk", n, cover, [s.stream for s in occupants],
                        id(toks)))
            list.append(self, entry)

    drain_ = eng._drain

    def reads(ph):
        head = eng._q_chunks[0] if eng._q_chunks else None
        pending = [st for st, _f in eng._pending_firsts]
        out = drain_(ph)
        read = [st for st in pending
                if all(st is not s for s, _f in eng._pending_firsts)]
        if head is not None and head[3] and any(
                st not in head[1] and not st.done for st in read):
            faults.append(("a first token read at a cover's drain",))
        log.append(("read", head and id(head[0]),
                    [st.stream for st in read]))
        return out

    monkeypatch.setattr(eng, "_q_chunks", Chunks())
    monkeypatch.setattr(eng, "_drain", reads)
    return log, faults


def first_with_its_chunk(log, stream) -> bool:
    """The stream's first token was read in the drain that read the first
    chunk that stepped it."""
    block = next(e[4] for e in log if e[0] == "chunk" and stream in e[3])
    return [e[1] for e in log if e[0] == "read" and stream in e[2]] == [block]


@pytest.mark.parametrize("case", [
    "a_parked_request_rides_behind_one_cover_step",
    "nobody_parked_nobody_stepped_past_the_end",
    "an_end_inside_a_cover_and_one_cover_past_it",
    "a_row_given_up_at_a_stop_token",
    "a_stream_closed_mid_decode"])
def test_a_row_changes_hands_behind_at_most_one_cover_step(
        case, pair, monkeypatch):
    """While the host reads an occupant's KNOWN last step, one chunk of one
    step steps the occupants who go on, and only if a parked request waits
    for the row (`watched` has the rules, held at every chunk of every
    case); each stream is the one its request gets alone.

    - parked: six requests, answers long enough that a first token is read
      before the answer's last step is dispatched. Each of the four later
      hand-overs rides behind exactly one chunk of one step, no pass before
      the last of them began dry, and every newcomer's first token came
      with its first chunk's tokens, in one drain, not at the cover's.
      With the last request seated nobody is parked: the next end is read
      with nothing past it and the pass after it begins dry.
    - nobody parked: two requests on two rows. No cover; the pipeline
      drains to the first end as it did.
    - an end inside a cover: the neighbour's last step is the cover's own.
      The next cover goes past THAT end once the first one's is read: two
      covers in flight, one past each end; the newcomer between them reads
      its first token with the second, which steps it.
    - a stop token, a closed stream: nobody's end was known, so nothing
      covers it, and the newcomer takes the row behind the chunks in flight
      and reads its first token at the next drain, as before."""
    def greedy(n, **kw):
        return SamplingParams(**GREEDY, max_tokens=n, **kw)

    requests = {
        "a_parked_request_rides_behind_one_cover_step": [
            ([1 + i, 2, 3 + i], greedy(n))
            for i, n in enumerate([21, 30, 44, 27, 39, 34])],
        "nobody_parked_nobody_stepped_past_the_end": [
            ([1, 2, 3], greedy(21)), ([2, 2, 4], greedy(30))],
        "an_end_inside_a_cover_and_one_cover_past_it": [
            ([1 + i, 2, 3 + i], greedy(n))
            for i, n in enumerate([21, 22, 30, 33])],
        "a_row_given_up_at_a_stop_token": [
            ([7, 7, 7], greedy(90)), ([4, 5], greedy(40)),
            ([9, 8], greedy(13))],
        "a_stream_closed_mid_decode": [
            ([6], greedy(70)), ([3, 1, 4], greedy(60)),
            ([2, 7, 1, 8], greedy(10))]}[case]
    want = [alone(pair, p, max_tokens=sp.max_tokens) for p, sp in requests]
    if case == "a_row_given_up_at_a_stop_token":
        stop = want[1][5]
        want[1] = want[1][:want[1].index(stop) + 1]
        requests[1] = (requests[1][0], greedy(40, stop_token=stop))
    at_rest(pair)
    before = pair.cache_stats()
    seen = hand_overs(pair, monkeypatch)
    log, faults = watched(pair, monkeypatch)
    streams = parked(pair, monkeypatch, requests)
    if case == "a_stream_closed_mid_decode":
        got = [streams[1].next(timeout=WAIT_S)]
        streams[1].close()
        got += drain(streams[1])
        assert got == want[1][:len(got)] and len(got) < 60
        want[1] = []
    assert [drain(s) for s in streams] == want
    at_rest(pair)
    assert faults == []
    got = counted(pair, before)
    assert got["splices"] == len(streams)
    covers = [e for e in log if e[0] == "chunk" and e[2]]
    assert got["cover_chunks"] == len(covers)
    behind = [seen[s][1:3] for s in streams]
    last = streams[-1]
    if case == "a_parked_request_rides_behind_one_cover_step":
        assert behind == [(0, [])] * 2 + [(1, [1])] * 4
        assert got["cover_chunks"] == 4 == got["splices_in_flight"]
        assert seen[last][3] == before["pipeline_dry"]
        assert got["pipeline_dry"] == 1  # the end after it: nobody parked
        assert all(first_with_its_chunk(log, s) for s in streams)
    elif case == "nobody_parked_nobody_stepped_past_the_end":
        assert behind == [(0, [])] * 2 and not covers
        assert got["pipeline_dry"] == 1 and got["splices_in_flight"] == 0
        # the chunk that holds the first end is read before another goes
        order = [e[0] for e in log]
        ends = [i for i, e in enumerate(log)
                if e[0] == "chunk" and len(e[3]) == 2][-1]
        assert order[ends + 1:ends + 5] == ["read"] * 4
    elif case == "an_end_inside_a_cover_and_one_cover_past_it":
        assert behind == [(0, []), (0, []), (1, [1]), (1, [1])]
        first, second = covers[:2]
        assert first[3] == [streams[1]] and second[3] == [streams[2]]
        assert [e[1] for e in log if e[0] == "read" and streams[2] in e[2]
                ] == [second[4]]
        assert all(first_with_its_chunk(log, s) for s in streams)
        # both covers were in flight at once, the second behind the first
        i, j = log.index(first), log.index(second)
        assert not any(e[0] == "read" and e[1] == first[4]
                       for e in log[i:j])
    else:
        assert not covers and got["pipeline_dry"] >= 1
        assert seen[last][0] == seen[streams[1]][0]
        assert seen[last][1] >= 1 and got["splices_in_flight"] == 1
        # the first token at the very next drain, whatever chunk it reads
        at = next(i for i, e in enumerate(log)
                  if e[0] == "chunk" and last in e[3])
        nxt = next(e for e in log[at:] if e[0] == "read")
        assert last in nxt[2]


@pytest.mark.parametrize("how", ["shutdown", "scheduler_error"])
def test_a_stream_with_chunks_in_flight_is_ended(how):
    """The engine stops, or its scheduler dies, between the dispatch of a
    request's chunks and their read: the stream ends all the same, with
    the error where there is one."""
    import threading

    eng = ContinuousEngine(CFG, max_batch=2, decode_chunk=4)
    reached, drain_, there = threading.Event(), eng._drain, []

    def stops_there(ph):
        if len(eng._q_chunks) < 2:
            return drain_(ph)
        there.append(([s and s.stream for s in eng._slots],
                      set(eng._streams)))
        reached.set()
        if how == "scheduler_error":
            raise RuntimeError("the read broke")
        until(lambda: not eng._running, "shutdown to begin")
        return None

    eng._drain = stops_there
    try:
        stream = eng.submit([1, 2, 3],
                            SamplingParams(**GREEDY, max_tokens=12))
        assert reached.wait(timeout=WAIT_S)
        assert there[0] == ([stream, None], {stream})
        if how == "shutdown":
            eng.shutdown()
            assert len(drain(stream)) < 12
        else:
            with pytest.raises(RuntimeError, match="scheduler died.*broke"):
                drain(stream)
            assert drain(stream) == []  # the error, then the end
        assert not eng._streams
    finally:
        eng.shutdown()
    assert not any(t.is_alive() for t in eng._threads)


def test_twenty_hand_overs_with_other_values_build_no_program(
        engine, monkeypatch):
    """One hand-over program whatever the row, the prompt's length within
    a bucket, `temperature`, `top_k`, `top_p` and seed: after one request
    of each kind has been through, twenty more build nothing, eager or
    jitted."""
    from ray_tpu._private import telemetry

    telemetry.ensure_compile_listener()
    # Alone in the batch a request of 7 tokens is dispatched whole in its
    # first pass, as chunks of 4, 2 and 1: every length, greedy and sampled.
    for temperature in (0.0, 0.8):
        assert len(drain(engine.submit([1, 2, 3], SamplingParams(
            temperature=temperature, top_k=4, top_p=0.9, max_tokens=7)))) == 7
    rows, splice = [], engine._splice

    def spy(slot, *rest):
        rows.append(slot)
        return splice(slot, *rest)

    monkeypatch.setattr(engine, "_splice", spy)
    built = telemetry.compile_stats()["count"]
    streams = [engine.submit(
        list(range(1, 2 + i % 7)),
        SamplingParams(temperature=(0.0, 0.3, 0.9, 1.7)[i % 4],
                       top_k=(0, 1, 5, 50)[i % 3 if i % 3 else 3],
                       top_p=(1.0, 0.95, 0.5)[i % 3], seed=1000 + i,
                       max_tokens=7))
        for i in range(20)]
    assert [len(drain(s)) for s in streams] == [7] * 20
    assert len(rows) == 20 and set(rows) == set(range(engine.max_batch))
    assert telemetry.compile_stats()["count"] == built


def test_streamed_text_does_not_depend_on_where_its_batches_were_cut():
    """A stream's batches are cut by timing. Its text must not be: bytes of
    one character that straddle two batches come out whole with the second
    (tests/test_stream_fallback.py compares two runs' streamed text)."""
    from ray_tpu.llm.openai import ByteTokenizer

    tok = ByteTokenizer()
    toks = list("aƺ€".encode()) + [0xE2, 0x82, 65, 257, 0xF0, 0x9F]
    want = tok.decode(toks)
    assert want == "aƺ€�A�"
    for cut in range(len(toks) + 1):
        decode = tok.stream_decoder()
        got = decode(toks[:cut]) + decode(toks[cut:]) + decode([], True)
        assert got == want, cut
