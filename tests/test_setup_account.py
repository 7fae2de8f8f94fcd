"""The process's set-up account (`ray_tpu/_private/telemetry.py`
`SetupAccount`; README "Tracing & timeline"): one record a program build from
JAX's own monitoring events, the stages around the builds, `/v1/stats`
`setup`, and, with RT_TRACING=1 only, `program.build` spans and the file
under the session directory. CPU: nothing here is a device number."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from ray_tpu._private import telemetry
from ray_tpu.llm import LLMConfig
from ray_tpu.llm.engine import ContinuousEngine, SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = LLMConfig(vocab_size=384, d_model=64, n_layers=2, n_heads=4,
                max_seq=128)
PARTS = ("trace_s", "lower_s", "compile_s", "retrieval_s")


def seven_tokens(engine, temperature):
    """Alone in the batch a request of 7 tokens is dispatched as chunks of
    4, 2 and 1 (decode_chunk 4)."""
    toks = engine.submit([1, 2, 3], SamplingParams(
        temperature=temperature, top_k=4, top_p=0.9, max_tokens=7)).tokens()
    assert len(toks) == 7


@pytest.fixture(scope="module")
def built():
    """(the account's builds, its stage seconds, what a plain listener of
    the one event the program used to hear counted) over one small engine's
    life: made inside the stages `OpenAIServer` writes, then one greedy and
    one sampled request."""
    import jax

    assert telemetry.ensure_compile_listener()
    plain = {"count": 0, "seconds": 0.0}

    def old_listener(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            plain["count"] += 1
            plain["seconds"] += float(duration)

    jax.monitoring.register_event_duration_secs_listener(old_listener)
    acct = telemetry.ACCOUNT
    before = telemetry.compile_stats()
    n0 = len(acct.builds)
    try:
        with telemetry.setup_stage("engine.init"):
            eng = ContinuousEngine(CFG, max_batch=4, decode_chunk=4)
        try:
            seven_tokens(eng, 0.0)
            seven_tokens(eng, 0.8)
        finally:
            eng.shutdown()
    finally:
        jax.monitoring.unregister_event_duration_listener(old_listener)
    after = telemetry.compile_stats()
    return {"builds": list(acct.builds)[n0:], "plain": plain,
            "stages": acct.stage_seconds(),
            "count": after["count"] - before["count"],
            "seconds": after["seconds"] - before["seconds"]}


def test_every_program_is_one_build_under_the_name_jax_gives_it(built):
    names = [b["fun_name"] for b in built["builds"]]
    # one bucket, one hand-over program, one first-token sampler
    for name in ("jit_prefill", "jit_place", "jit_sample1"):
        assert names.count(name) == 1, names
    # the chunk program: traced anew for every length and sampler, so the
    # lengths 4, 2, 1, greedy and sampled, are six builds: five at the first
    # request that needs each, and the longest sampled one inside
    # `engine.programs`, where `_count_boundary_copies` compiles it (beside
    # the layout probe's one-layer program)
    chunks = [b for b in built["builds"] if b["fun_name"] == "jit_chunk"]
    assert [b["stage"] for b in chunks].count("engine.programs") == 2
    assert len([b for b in chunks if b["stage"] is None]) == 5
    # the parameter-making program is the constructor's lambda
    assert [b["stage"] for b in built["builds"]
            if b["fun_name"] == "jit__lambda_"] == ["engine.params"]
    for b in built["builds"]:
        assert b["a"] <= b["b"] and b["cache"] in ("hit", "miss", "off")
        assert all(b[k] >= 0 for k in PARTS), b
        # the three parts lie inside the build's own wall interval
        assert (b["trace_s"] + b["lower_s"] + b["compile_s"]
                <= b["b"] - b["a"] + 1e-6)
    # a jit's own trace is found among the traces of what it calls
    assert all(b["trace_s"] > 0 and b["lower_s"] > 0 for b in built["builds"]
               if b["fun_name"] in ("jit_prefill", "jit_chunk", "jit_place"))


def test_the_stages_are_kept_with_tracing_off(built):
    st = built["stages"]
    assert {"engine.init", "engine.params", "engine.programs",
            "engine.cache_alloc"} <= set(st)
    assert st["engine.init"] >= st["engine.params"] + st["engine.programs"]
    assert st["engine.cache_alloc"] > 0


def test_compile_count_and_seconds_are_the_old_listeners(built):
    assert built["count"] == built["plain"]["count"] == len(built["builds"])
    assert built["seconds"] == pytest.approx(built["plain"]["seconds"],
                                             rel=1e-9)
    assert sum(b["compile_s"] for b in built["builds"]) == pytest.approx(
        built["plain"]["seconds"], rel=1e-9)


def test_the_summary_adds_up_by_name(built):
    got = telemetry.ACCOUNT.summary()
    assert got["builds"] == telemetry.compile_stats()["count"]
    chunk = got["programs"]["jit_chunk"]
    assert chunk["builds"] >= 7  # the probe, six lengths and samplers
    assert chunk["hits"] + chunk["misses"] <= chunk["builds"]
    assert set(PARTS) <= set(chunk)
    line = telemetry.ACCOUNT.one_line()
    assert f"{got['builds']} builds" in line and "compile_s" in line


# ------------------------------------------- a build that changes threads
def on_a_thread(fn):
    import threading

    t = threading.Thread(target=fn)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive()


@pytest.fixture
def handed_over():
    """A program traced and lowered on one thread and compiled on another,
    as `llm/programs.py` builds: (its records in the account, the record the
    compiling thread was given, what the lowering thread still held)."""
    import jax
    import jax.numpy as jnp

    assert telemetry.ensure_compile_listener()
    acct, box = telemetry.ACCOUNT, {}
    n0 = len(acct.builds)

    def moved_between_threads(x):
        return jnp.tanh(x @ x.T).sum()

    def lower():
        box["lowered"] = jax.jit(moved_between_threads).lower(
            jax.ShapeDtypeStruct((32, 32), jnp.float32))
        box["handed"] = acct.hand_over()
        box["left"] = acct._thread().lower

    def compile_():
        acct.take_over(box["handed"])
        box["lowered"].compile()
        box["rec"] = acct.built()
        box["after"] = acct.built()

    on_a_thread(lower)
    on_a_thread(compile_)
    box["recs"] = [b for b in list(acct.builds)[n0:]
                   if b["fun_name"] == "jit_moved_between_threads"]
    return box


def test_a_build_whose_compile_ends_on_another_thread_is_one_record(
        handed_over):
    (rec,) = handed_over["recs"]
    assert rec is handed_over["rec"]
    assert handed_over["left"] is None and handed_over["after"] is None
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0 and rec["compile_s"] > 0
    # `a` the trace's start, `b` the compile's end, whatever lay between
    assert (rec["trace_s"] + rec["lower_s"] + rec["compile_s"]
            <= rec["b"] - rec["a"] + 1e-6)
    assert rec["cache"] in ("hit", "miss", "off") and "ahead" not in rec
    assert telemetry.ACCOUNT.by_name["jit_moved_between_threads"][
        "builds"] == 1


def test_a_first_call_of_a_program_built_ahead_is_tied_to_its_build(
        handed_over, monkeypatch):
    """Nobody had asked when the build ended (`ahead`); the first call that
    takes the program is still the build's call: `call_a`, `call_s` and,
    once its result is read, `ready_s`, on the record and on its span."""
    import time

    from ray_tpu._private import tracing

    acct, rec, spans = telemetry.ACCOUNT, handed_over["rec"], []
    monkeypatch.setattr(tracing, "_ON", True)
    monkeypatch.setattr(tracing, "record_span",
                        lambda *a: spans.append(a))
    monkeypatch.setattr(acct, "write", lambda: None)
    acct.ahead(rec)
    ctx = ("ab" * 16, "cd" * 8)
    acct.begin_call(ctx)
    acct.first_call(rec)  # where the call site takes it from the table
    time.sleep(0.01)
    built = acct.end_call(bucket=8, kernel=False)
    assert built == [rec] and spans == []  # the span waits for the read
    assert rec["call_a"] >= rec["b"]  # built before anyone called
    assert rec["call_s"] >= 0.01 and rec["bucket"] == 8
    acct.builds_ready(built, rec["call_a"] + 0.25)
    assert rec["ready_s"] == pytest.approx(0.25) and "ctx" not in rec
    ((trace, _span, parent, name, kind, a, b, attrs),) = spans
    assert (trace, parent, name, kind) == (*ctx, "program.build", "engine")
    assert (a, b) == (rec["a"], rec["b"])
    assert attrs["ahead"] is True and attrs["ready_s"] == rec["ready_s"]
    assert attrs["call_a"] == rec["call_a"]


def test_a_first_call_outside_any_call_is_a_child_of_the_stage_it_is_in(
        handed_over, monkeypatch):
    from ray_tpu._private import tracing

    acct, rec, spans = telemetry.ACCOUNT, handed_over["rec"], []
    monkeypatch.setattr(tracing, "_ON", True)
    monkeypatch.setattr(tracing, "record_span", lambda *a: spans.append(a))
    monkeypatch.setattr(acct, "write", lambda: None)
    with telemetry.setup_stage("engine.programs"):
        acct.first_call(rec)  # as `_count_boundary_copies` takes its program
    assert rec["stage"] == "engine.programs" and "call_a" not in rec
    build = next(s for s in spans if s[3] == "program.build")
    stage = next(s for s in spans if s[3] == "engine.programs")
    assert build[2] == stage[1] and build[0] == stage[0]


class _Stats:
    path = "/v1/stats"


#: What `/v1/stats` answered before the account (`llm/openai.py` at PR 54).
STATS_KEYS = {
    "pid", "active", "running", "served", "runtime_init_s", "engine_init_s",
    "platform", "device_kind", "device_ids", "chip_files_open",
    "tpu_visible_chips", "compile_count", "compile_s", "memory_peak_bytes",
    "cache_layout", "cache_boundary_copies", "kv_walk_share",
    "kv_live_share", "cache_kinds", "splices", "splices_in_flight",
    "pipeline_dry", "decode_steps", "decode_steps_kernel", "prefill_rows",
    "prefill_rows_kernel", "sampler_steps", "sampler_steps_select"}


def test_v1_stats_keeps_its_keys_and_gains_setup():
    from ray_tpu.llm.openai import OpenAIServer

    server = OpenAIServer(CFG, max_batch=2, decode_chunk=4)
    try:
        st = server(_Stats())
    finally:
        server.engine.shutdown()
    assert STATS_KEYS <= set(st), STATS_KEYS - set(st)
    setup = st["setup"]
    assert st["runtime_init_s"] == setup["stages"]["runtime.init"] >= 0
    assert st["engine_init_s"] == setup["stages"]["engine.init"] > 0
    assert st["compile_count"] == setup["builds"] > 0
    assert st["compile_s"] == pytest.approx(sum(
        p["compile_s"] for p in setup["programs"].values()), abs=0.01)
    # how the serving programs came to be (`llm/programs.py`): no list here
    assert {k: setup[k] for k in (
        "programs_ahead", "programs_waited", "programs_on_demand",
        "list_unused")} == {"programs_ahead": 0, "programs_waited": 0,
                            "programs_on_demand": 2, "list_unused": 0}
    json.dumps(st)  # what the proxy has to serialise


# --------------------------------------------------------- other processes
def run_py(code: str, env: dict) -> dict:
    """`code` in a process of its own; its last line of output, as JSON."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT, **env}
    for k in ("RT_TRACING", "JAX_COMPILATION_CACHE_DIR"):
        if k not in env or env[k] is None:
            env.pop(k, None)
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


CACHED = """
    import json
    import jax, jax.numpy as jnp
    from ray_tpu._private import telemetry
    telemetry.ensure_compile_listener()

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x.T).sum()

    step(jnp.ones((64, 64))).block_until_ready()
    print(json.dumps([b for b in telemetry.ACCOUNT.builds
                      if b["fun_name"] == "jit_step"]))
"""


def test_a_second_process_on_the_same_cache_reads_a_hit(tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    (first,), (second,) = run_py(CACHED, env), run_py(CACHED, env)
    assert first["cache"] == "miss" and first["retrieval_s"] == 0
    assert second["cache"] == "hit" and second["retrieval_s"] > 0
    assert second["compile_s"] >= second["retrieval_s"]
    assert second["trace_s"] > 0 and second["lower_s"] > 0
    # no directory: the cache has no part in the build
    (third,) = run_py(CACHED, {"JAX_COMPILATION_CACHE_DIR": None})
    assert third["cache"] == "off"


RACED = """
    import json, sys, threading
    from ray_tpu._private import telemetry
    assert telemetry.ensure_compile_listener() is False  # no jax yet
    import jax
    sys.setswitchinterval(1e-6)
    gate = threading.Barrier(16)
    def ask():
        gate.wait(timeout=30)
        assert telemetry.ensure_compile_listener()
    threads = [threading.Thread(target=ask) for _ in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    from jax._src import monitoring as mon
    acct = telemetry.ACCOUNT
    jax.jit(lambda x: x + 1)(1.0)
    print(json.dumps({
        "span": mon.get_event_time_span_listeners().count(acct.on_time_span),
        "event": mon.get_event_listeners().count(acct.on_event),
        "secs": mon.get_event_duration_listeners().count(acct.on_duration),
        "builds": [b["fun_name"] for b in acct.builds]}))
"""


def test_the_listeners_are_registered_once_whoever_asks(tmp_path):
    """The sampler's thread asks on every tick and the server's constructor
    asks too: registered twice, every build was counted twice (the first
    chip run of PR 56 read 80 programs where the parent read 40)."""
    got = run_py(RACED, {})
    assert (got["span"], got["event"], got["secs"]) == (1, 1, 1)
    assert got["builds"].count("jit__lambda_") == 1


SERVED = """
    import json, os
    from ray_tpu._private import telemetry, tracing
    spans = []
    record = tracing.record_span
    def keep(t, s, p, n, k, a, b, at=None):
        spans.append(dict(t=t, s=s, p=p, n=n, k=k, at=at or {}))
        return record(t, s, p, n, k, a, b, at)
    tracing.record_span = keep
    import jax
    telemetry.ensure_compile_listener()
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.engine import ContinuousEngine, SamplingParams
    cfg = LLMConfig(vocab_size=384, d_model=64, n_layers=2, n_heads=4,
                    max_seq=128)
    with telemetry.setup_stage("replica.start", deployment="llm"):
        with telemetry.setup_stage("runtime.init"):
            jax.local_devices()
        with telemetry.setup_stage("engine.init"):
            eng = ContinuousEngine(cfg, max_batch=4, decode_chunk=4)
    if tracing.enabled():
        tracing._ctx.set(("ab" * 16, "cd" * 8))  # a request's context
    path = os.path.join(os.environ["RT_SESSION_DIR"], "setup",
                        f"{os.getpid()}.json")
    def served(n_prompt):
        eng.submit(list(range(1, n_prompt + 1)), SamplingParams(
            temperature=0.0, max_tokens=7)).tokens()
        return json.load(open(path)) if os.path.exists(path) else None
    first = served(3)
    late = served(20)  # another bucket: a prefill and a place program more
    eng.shutdown()
    print(json.dumps({
        "ring": tracing._ring is not None, "spans": spans, "first": first,
        "late": late, "pid": os.getpid(),
        "files": os.listdir(os.environ["RT_SESSION_DIR"]),
        "count": telemetry.compile_stats()["count"]}))
"""


def test_tracing_off_records_no_span_no_ring_and_no_file(tmp_path):
    got = run_py(SERVED, {"RT_SESSION_DIR": str(tmp_path), "RT_TRACING": None})
    assert got["ring"] is False and got["spans"] == []
    assert got["files"] == [] and got["first"] is None
    assert got["count"] > 10  # the account itself was kept


def test_tracing_on_a_build_is_a_child_of_what_caused_it(tmp_path):
    got = run_py(SERVED, {"RT_SESSION_DIR": str(tmp_path), "RT_TRACING": "1"})
    spans = got["spans"]
    by_id = {s["s"]: s for s in spans}
    builds = [s for s in spans if s["n"] == "program.build"]
    assert all(s["k"] == "engine" for s in builds)

    def parents(name):
        return [by_id[s["p"]]["n"] if s["p"] in by_id else s["p"]
                for s in builds if s["at"]["fun_name"] == name]

    # prefill, first-token sampler and hand-over: the request's prefill
    assert parents("jit_prefill") == ["engine.prefill"] * 2
    assert parents("jit_sample1") == ["engine.prefill"]
    assert parents("jit_place") == ["engine.prefill"] * 2
    # a chunk program: the context its engine.dispatch_chunk takes
    assert set(parents("jit_chunk")) == {"engine.programs", "cd" * 8}
    assert parents("jit__lambda_") == ["engine.params"]
    # the stages: one root of kind `setup`, the others inside it
    root = next(s for s in spans if s["n"] == "replica.start")
    assert root["k"] == "setup" and root["p"] is None
    assert root["at"] == {"deployment": "llm"}
    for name in ("runtime.init", "engine.init"):
        st = next(s for s in spans if s["n"] == name)
        assert (st["k"], st["p"], st["t"]) == ("setup", root["s"], root["t"])
    alloc = next(s for s in spans if s["n"] == "engine.cache_alloc")
    assert by_id[alloc["p"]]["n"] == "engine.prefill"
    # what the engine adds to a build: the call it lay in and its result
    pre = next(s["at"] for s in builds if s["at"]["fun_name"] == "jit_prefill")
    assert pre["bucket"] == 8 and pre["kernel"] is False
    assert pre["ready_s"] >= pre["call_s"] >= (
        pre["trace_s"] + pre["lower_s"] + pre["compile_s"])
    chunk = next(s["at"] for s in builds if s["at"].get("tokens") == 4)
    assert chunk["sampler"] == "greedy" and chunk["ready_s"] >= chunk["call_s"]
    # the file: this process's, rewritten after the late builds
    assert got["files"] == ["setup"]
    first, late = got["first"], got["late"]
    assert first["pid"] == late["pid"] == got["pid"]
    assert first["process_start"] == late["process_start"] < first["written"]
    assert late["written"] > first["written"]
    assert len(late["builds"]) > len(first["builds"])
    assert late["compile_count"] == len(late["builds"]) == got["count"]
    assert {s["n"] for s in late["stages"]} >= {
        "replica.start", "runtime.init", "engine.init", "engine.params",
        "engine.programs", "engine.cache_alloc"}
    assert [b["fun_name"] for b in late["builds"]].count("jit_prefill") == 2
    assert all("ctx" not in b for b in late["builds"])


def test_a_traced_start_from_a_list_accounts_for_every_first_call(tmp_path):
    """A second start on the same cache directory finds the first's list and
    builds AHEAD (`llm/programs.py`): a build is still one record, those that
    ended before anyone asked say `ahead`, and the first call of each
    serving program still carries `call_a` / `call_s` / `ready_s`, so
    `setup_first_run_s` goes on reading the first calls' waits."""
    env = {"RT_TRACING": "1",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    runs = []
    for name in ("first", "second"):
        session = tmp_path / name
        session.mkdir()
        runs.append(run_py(SERVED, {**env, "RT_SESSION_DIR": str(session)}))
    (lists,) = os.listdir(tmp_path / "cache" / "programs")
    serving = ("jit_prefill", "jit_place", "jit_sample1", "jit_chunk")
    for got, ahead in zip(runs, (False, True)):
        builds = [b for b in got["late"]["builds"]
                  if b["fun_name"] in serving]
        assert len(got["late"]["builds"]) == got["count"]
        assert any(b.get("ahead") for b in builds) is ahead
        for b in builds:
            assert b["trace_s"] > 0 and b["lower_s"] > 0 and b["compile_s"] > 0
            # every one was called (the probe and the longest sampled chunk
            # inside `engine.programs`, the others by a request)
            assert "call_a" in b or b["stage"] == "engine.programs", b
            # (a hand-over's result is never read, nor that of a chunk whose
            # occupants had all ended when its block came: no `ready_s`)
            if b["fun_name"] in ("jit_prefill", "jit_sample1"):
                assert b["ready_s"] >= b["call_s"] > 0
            assert b.get("ready_s", b.get("call_s", 1)) > 0
            if b.get("ahead"):  # (its call may have BEGUN before it ended:
                assert b["cache"] == "hit"  # the site asks inside the call)
        spans = [s for s in got["spans"] if s["n"] == "program.build"
                 and s["at"]["fun_name"] in serving]
        assert len(spans) == len(builds)
        assert sum(bool(s["at"].get("ahead")) for s in spans) == sum(
            bool(b.get("ahead")) for b in builds)
    names = [[b["fun_name"] for b in got["late"]["builds"]
              if b["fun_name"] in serving] for got in runs]
    assert sorted(names[0]) == sorted(names[1])  # the same programs, once each
