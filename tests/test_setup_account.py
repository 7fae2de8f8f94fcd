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
