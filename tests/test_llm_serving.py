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
                       "kv_live_share"}
    assert 0.0 <= st["kv_live_share"] <= st["kv_walk_share"] <= 1.0
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
        full.setattr(engine, "_free_slot", lambda taken=(): None)
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
    eng._free_slot = lambda taken=(): None  # no slot comes free
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
