"""Token-batch stream ring + the zero-sync serving reply path.

README "Serving hot loop": ring-level invariants (FIFO across wrap,
bounded-buffer backpressure, batch-per-wakeup draining), the
RT_TOKEN_RING=0 byte-identical fallback, and the chaos cases — client
disconnect mid-generation retires the engine slot (no slot leak),
engine-scheduler death and replica death surface attributed errors on
every open stream, never a hang.
"""

import glob
import json
import os
import socket
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.dag.stream import RingClosed, StreamRing

CFG_KW = dict(vocab_size=384, d_model=64, n_layers=2, n_heads=4,
              max_seq=128)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


# ------------------------------------------------------------- ring level
def test_ring_fifo_no_loss_across_wrap():
    """2000 records through a 4KB ring: every record arrives, in order —
    the ring wraps dozens of times (slot reuse at the byte level)."""
    ring = StreamRing(f"t_fifo_{os.getpid()}", 4096)
    n = 2000
    got: list = []

    def produce():
        for i in range(n):
            ring.write(("rec", i, b"x" * (i % 40)), timeout=30)
        ring.close_write()

    t = threading.Thread(target=produce, daemon=True)
    t.start()
    try:
        while True:
            try:
                got.extend(ring.read_batch(timeout=30))
            except RingClosed:
                break
        assert [r[1] for r in got] == list(range(n))
        assert all(r[2] == b"x" * (r[1] % 40) for r in got)
    finally:
        t.join(timeout=10)
        ring.close(unlink=True)


def test_ring_read_batch_drains_burst_in_one_wakeup():
    ring = StreamRing(f"t_batch_{os.getpid()}", 1 << 16)
    try:
        for i in range(10):
            ring.write(i)
        assert ring.read_batch(timeout=1) == list(range(10))
        with pytest.raises(TimeoutError):
            ring.read_batch(timeout=0.05)
    finally:
        ring.close(unlink=True)


def test_ring_backpressure_producer_parks_bounded():
    """No consumer: the producer fills the BOUNDED ring then parks (write
    times out) instead of buffering unboundedly; a consumer draining later
    receives everything written, in order, and unparks further writes."""
    cap = 4096
    ring = StreamRing(f"t_bp_{os.getpid()}", cap)
    try:
        written = 0
        payload = b"y" * 100
        with pytest.raises(TimeoutError):
            while True:
                ring.write((written, payload), timeout=0.05)
                written += 1
        # Parked at the capacity bound: nothing close to unbounded growth.
        assert 0 < written <= cap // 100
        got = ring.read_batch(timeout=1)
        assert [r[0] for r in got] == list(range(written))
        ring.write((written, payload), timeout=1)  # space freed: unparked
        assert ring.read_batch(timeout=1)[0][0] == written
    finally:
        ring.close(unlink=True)


def test_ring_close_write_then_drained_raises():
    ring = StreamRing(f"t_close_{os.getpid()}", 4096)
    try:
        ring.write("a")
        ring.write("b")
        ring.close_write()
        assert ring.read_batch(timeout=1) == ["a", "b"]
        with pytest.raises(RingClosed):
            ring.read_batch(timeout=1)
        with pytest.raises(RingClosed):
            ring.write("c")
    finally:
        ring.close(unlink=True)


def test_ring_oversize_record_rejected():
    ring = StreamRing(f"t_big_{os.getpid()}", 4096)
    try:
        with pytest.raises(ValueError, match="record"):
            ring.write(b"z" * 4096)
    finally:
        ring.close(unlink=True)


def test_ring_attach_requires_existing():
    with pytest.raises(FileNotFoundError):
        StreamRing(f"t_missing_{os.getpid()}", 4096, _create=False)
    ring = StreamRing(f"t_attach_{os.getpid()}", 8192)
    try:
        peer = StreamRing.attach(ring.spec())
        ring.write("hello")
        assert peer.read_batch(timeout=1) == ["hello"]
        peer.close()
    finally:
        ring.close(unlink=True)


# ------------------------------------------------------------ engine level
@pytest.fixture(scope="module")
def engine():
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.engine import ContinuousEngine

    eng = ContinuousEngine(LLMConfig(**CFG_KW), max_batch=4, decode_chunk=4)
    yield eng
    eng.shutdown()


def test_genstream_batch_delivery_one_wakeup_per_chunk(engine):
    """GenStream delivers token BATCHES: draining 32 tokens takes far
    fewer next_batch wakeups than tokens (one queue put per decode chunk,
    not per token — the satellite's no-per-token-wakeup pin)."""
    from ray_tpu.llm.engine import SamplingParams

    s = engine.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                max_tokens=32))
    batches = []
    while True:
        try:
            batches.append(s.next_batch(timeout=60))
        except StopIteration:
            break
    toks = [t for b in batches for t in b]
    assert len(toks) == 32
    # 32 tokens at decode_chunk=4 is ~9 queue puts (first token + 8
    # chunks); a per-token queue would need 32 wakeups.
    assert len(batches) <= 16, f"{len(batches)} wakeups for 32 tokens"
    # Batched delivery preserves the exact greedy sequence.
    ref = engine.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                  max_tokens=32)).tokens()
    assert toks == ref


def test_disconnect_churn_retires_slots_no_leak(engine):
    """Chaos satellite: consumers abandoning streams mid-generation (the
    client-disconnect shape) retire their slots and free KV/sampling
    state — 24 churned requests across 8 rounds reuse the same 4 slots
    and the engine drains to zero active every round."""
    from ray_tpu.llm.engine import SamplingParams

    for _ in range(8):
        streams = [engine.submit([7, 8, 9], SamplingParams(
            temperature=0.0, max_tokens=100)) for _ in range(3)]
        for s in streams:
            s.next(timeout=60)  # slot is live and decoding
            s.close()  # client gone
        deadline = time.monotonic() + 30
        while engine.num_active > 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert engine.num_active == 0, "abandoned slots leaked"
    # The engine still serves fresh requests with exact token counts.
    toks = engine.submit([7, 8, 9], SamplingParams(
        temperature=0.0, max_tokens=12)).tokens()
    assert len(toks) == 12


def test_engine_scheduler_death_attributed_never_hangs():
    """Chaos satellite: the engine scheduler dying mid-stream surfaces an
    attributed error on EVERY open GenStream promptly — a consumer
    blocked in next() must never hang on a dead engine."""
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.engine import ContinuousEngine, SamplingParams

    eng = ContinuousEngine(LLMConfig(**CFG_KW), max_batch=4, decode_chunk=4)
    try:
        streams = [eng.submit([1, 2], SamplingParams(
            temperature=0.0, max_tokens=120)) for _ in range(2)]
        for s in streams:
            s.next(timeout=60)  # both decoding
        eng._slots = None  # scheduler's next iteration dies uncaught
        for s in streams:
            with pytest.raises(RuntimeError, match="scheduler died"):
                deadline = time.monotonic() + 15
                while time.monotonic() < deadline:
                    s.next(timeout=15)
        assert not eng._running
        with pytest.raises(RuntimeError, match="shut down"):
            eng.submit([1], SamplingParams(max_tokens=1))
    finally:
        eng.shutdown()


# -------------------------------------------------------------- HTTP level
def _openai_app(port, **kw):
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.openai import build_openai_app

    from ray_tpu import serve

    app = build_openai_app(LLMConfig(**CFG_KW), model_id="ring-llm",
                           max_batch=4, decode_chunk=4,
                           default_max_tokens=8, **kw)
    serve.run(app, route_prefix="/", port=port)


def _sse_request(base, max_tokens, timeout=120):
    body = json.dumps({"prompt": "hi", "max_tokens": max_tokens,
                       "temperature": 0.0, "stream": True}).encode()
    return urllib.request.Request(
        f"{base}/v1/completions", data=body,
        headers={"Content-Type": "application/json"})


def _drain_sse(resp):
    toks, events = [], 0
    for line in resp:
        line = line.decode().strip()
        if not line.startswith("data: "):
            continue
        payload = line[6:]
        if payload == "[DONE]":
            break
        events += 1
        toks.extend(json.loads(payload).get("token_ids", []))
    return toks, events


def _stats(base):
    with urllib.request.urlopen(f"{base}/v1/stats", timeout=30) as r:
        return json.loads(r.read())


def test_sse_ring_concurrent_clients_fifo_no_loss(shutdown_only):
    """4 concurrent streaming clients over the token ring: every client
    receives its full greedy sequence in order (no token loss or cross-
    slot mixing across engine slot reuse), and multi-token arrivals
    coalesce into fewer SSE events than tokens."""
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4)
    port = _free_port()
    _openai_app(port)
    base = f"http://127.0.0.1:{port}"
    try:
        results: dict = {}

        def client(i):
            with urllib.request.urlopen(_sse_request(base, 24),
                                        timeout=180) as r:
                results[i] = _drain_sse(r)

        for round_ in range(2):  # second round reuses the freed slots
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            seqs = [tuple(results[i][0]) for i in range(4)]
            assert all(len(s) == 24 for s in seqs), seqs
            # Same greedy prompt => identical sequences on every client.
            assert len(set(seqs)) == 1
        # Coalescing: a 24-token stream arrives in well under 24 events.
        _toks, events = results[0]
        assert events < 24, f"{events} SSE events for 24 tokens"
        assert _stats(base)["active"] == 0
    finally:
        serve.shutdown()


def _own_rings() -> list:
    """The SSE stream rings in /dev/shm that a process of THIS test's
    cluster made (a ring's name embeds its creator's pid, and the cluster's
    processes descend from this one): under `-n 6` the neighbours' tests
    stream through rings of their own beside it."""
    import psutil

    mine = {str(p.pid) for p in psutil.Process().children(recursive=True)}
    return [path for path in glob.glob("/dev/shm/rtring_sse_*")
            if os.path.basename(path).split("_")[2] in mine]


def test_sse_token_ring_off_byte_identical_fallback(monkeypatch,
                                                    shutdown_only):
    """RT_TOKEN_RING=0: the classic per-item streaming-generator reply
    path serves the stream — and no stream ring is ever created."""
    from ray_tpu import serve

    monkeypatch.setenv("RT_TOKEN_RING", "0")
    ray_tpu.init(num_cpus=4)
    port = _free_port()
    _openai_app(port)
    base = f"http://127.0.0.1:{port}"
    try:
        rings_seen = []
        toks = []
        with urllib.request.urlopen(_sse_request(base, 12),
                                    timeout=180) as r:
            for line in r:
                rings_seen.extend(_own_rings())
                line = line.decode().strip()
                if not line.startswith("data: "):
                    continue
                if line[6:] == "[DONE]":
                    break
                toks.extend(json.loads(line[6:]).get("token_ids", []))
        assert len(toks) == 12
        assert rings_seen == [], f"knob off but rings exist: {rings_seen}"
    finally:
        serve.shutdown()


def test_sse_client_disconnect_frees_engine_slot(shutdown_only):
    """Chaos satellite at the HTTP layer: a client dropping its SSE
    connection mid-generation retires the engine slot (observed via
    /v1/stats) instead of decoding to max_tokens for nobody."""
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4)
    port = _free_port()
    _openai_app(port)
    base = f"http://127.0.0.1:{port}"
    try:
        # Warm the engine (first request pays the compiles).
        with urllib.request.urlopen(_sse_request(base, 4), timeout=180) as r:
            _drain_sse(r)
        r = urllib.request.urlopen(_sse_request(base, 120), timeout=180)
        r.readline()  # first SSE event: the stream is live
        assert _stats(base)["active"] >= 1
        r.close()  # client disconnect
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if _stats(base)["active"] == 0:
                break
            time.sleep(0.1)
        assert _stats(base)["active"] == 0, "disconnected stream leaked slot"
        # The replica still serves a full request afterwards.
        with urllib.request.urlopen(_sse_request(base, 6), timeout=180) as r:
            toks, _ = _drain_sse(r)
        assert len(toks) == 6
    finally:
        serve.shutdown()


def test_sse_replica_death_attributed_never_hangs(shutdown_only):
    """Chaos satellite: the engine-hosting replica dying mid-stream ends
    every open SSE stream with an ATTRIBUTED error event within the
    failure-detection deadline — never a hang."""
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4)
    port = _free_port()
    _openai_app(port)
    base = f"http://127.0.0.1:{port}"
    try:
        with urllib.request.urlopen(_sse_request(base, 4), timeout=180) as r:
            _drain_sse(r)  # warm compiles
        pid = _stats(base)["pid"]
        r = urllib.request.urlopen(_sse_request(base, 120), timeout=60)
        r.readline()  # stream is live
        os.kill(pid, 9)
        lines = []
        t0 = time.monotonic()
        try:
            for line in r:
                lines.append(line.decode().strip())
                if lines[-1] == "data: [DONE]":
                    break
        except Exception as e:  # connection torn down is also a fast end
            lines.append(f"connection-error: {e!r}")
        took = time.monotonic() - t0
        assert took < 45, f"stream hung {took:.0f}s after replica death"
        err_lines = [ln for ln in lines if "error" in ln.lower()]
        assert err_lines, f"no attributed error surfaced: {lines[-3:]}"
        assert any("actor" in ln.lower() or "died" in ln.lower()
                   or "connection-error" in ln for ln in err_lines), err_lines
    finally:
        serve.shutdown()
