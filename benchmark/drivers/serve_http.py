"""The driver for cells that serve completions over HTTP: it deploys the
configuration's application through the program's own entry points
(`ray_tpu.init`, `serve.run`), offers the traffic mix from this process,
and takes every end-to-end number on the client's side of HTTP.

This process never imports JAX: a chip belongs to one process, and the
replicas hold the chips. Whatever needs JAX runs elsewhere: the replicas
(the system under test), the reference actor (after the replicas are gone)
and the trace reduction (a child held to the CPU).

Order of a run: cluster, deployment, lone warm-up requests (every program
the mix can reach), `preload_s` of the mix itself, the measured window, a
drain in which the same traffic goes on until the window's requests have
ended, then shutdown of the deployment and the plain reference on the freed
chip. Everything before the window is `setup_s`.
"""

from __future__ import annotations

import asyncio
import importlib
import json
import math
import os
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request
import zipfile

from benchmark import manifest, stats, traffic as traffic_mod

T_PROCESS_START = time.monotonic()


#: The workers flush their spans to the controller once a second.
FLUSH_WAIT_S = 2.5


class RunFailed(Exception):
    """The run cannot give a result; the process exits non-zero."""


def say(msg: str) -> None:
    print(f"[{time.monotonic() - T_PROCESS_START:7.1f}s] {msg}", flush=True)


# ------------------------------------------------------------------ set-up
def preflight(config: dict, chips_needed: int) -> None:
    """Chips of this host, found without JAX. A configuration that states
    `platform: tpu` (every one outside the tests) is never served from
    anything else."""
    if config.get("platform", "tpu") != "tpu":
        return
    held_to = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if held_to and "tpu" not in held_to.split(","):
        raise RunFailed(f"no TPU: JAX_PLATFORMS={held_to} holds this run to "
                        f"another platform; nothing was served")
    from ray_tpu._private.accelerators import num_tpu_chips

    chips = num_tpu_chips()
    if chips < chips_needed:
        raise RunFailed(f"this host has {chips} TPU chip(s), the cell needs "
                        f"{chips_needed}; nothing was served")


def place_state() -> str:
    """Everything the program writes at run time goes under TMPDIR or the
    checkout, never to a fixed path: the driver gives each side of a
    comparison a TMPDIR of its own. Returns the session directory."""
    from ray_tpu._private import compile_cache

    session = os.path.join(tempfile.gettempdir(), "ray_tpu_bench")
    os.environ["RT_SESSION_DIR"] = session
    os.environ["RT_OBJECT_SPILL_DIR"] = os.path.join(session, "spill")
    os.environ["RT_TRACE_DIR"] = "none"  # spans are read from the controller
    # The compile cache: the program's one rule (an outside directory is
    # taken as it is, otherwise <checkout>/.jax_cache, every program kept).
    compile_cache.apply(os.environ)
    root = manifest.ROOT
    os.environ["PYTHONPATH"] = root + (
        os.pathsep + os.environ["PYTHONPATH"]
        if os.environ.get("PYTHONPATH") else "")
    # The peak of device memory comes from the program's telemetry sampler
    # (hbm_peak, every 2 s in each worker that holds a device).
    os.environ.setdefault("RT_TELEMETRY_INTERVAL_S", "2")
    return session


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def build_app(config: dict):
    mod_name, fn_name = config["app"].split(":")
    builder = getattr(importlib.import_module(mod_name), fn_name)
    from ray_tpu.llm import LLMConfig

    return builder(LLMConfig(**config["llm_config"]), **config["app_kwargs"])


def get_json(url: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def replica_stats(base: str, n: int, timeout: float = 60.0) -> list[dict]:
    """/v1/stats answers from one replica per call; ask until every replica
    (one pid each) has answered."""
    seen: dict[int, dict] = {}
    t_end = time.monotonic() + timeout
    while len(seen) < n and time.monotonic() < t_end:
        st = get_json(f"{base}/v1/stats")
        seen[st["pid"]] = st
    if len(seen) < n:
        raise RunFailed(f"only {len(seen)} of {n} replicas answered /v1/stats")
    return [seen[p] for p in sorted(seen)]


def shed_total(stats_: list[dict]) -> int:
    """Requests the deployment has shed so far, over its replicas."""
    return sum((s.get("serve") or {}).get("shed_total", 0) for s in stats_)


def check_devices(stats_: list[dict], config: dict) -> list[str]:
    """Every replica runs on the platform the configuration states, and
    every one-chip replica holds open one chip file of its own."""
    bad = []
    want = config.get("platform", "tpu")
    for st in stats_:
        if st["platform"] != want:
            bad.append(f"replica {st['pid']} runs on {st['platform']!r}, "
                       f"not {want!r}")
    if want == "tpu":
        held = [tuple(st["chip_files_open"]) for st in stats_]
        if any(len(h) != 1 for h in held) or len(set(held)) != len(held):
            bad.append(f"replicas do not each hold one chip of their own "
                       f"open: {held}")
    return bad


# --------------------------------------------------------------- requests
class Record:
    """One request as the client saw it. Times are time.monotonic()."""

    __slots__ = ("due", "sent", "t_first", "t_last", "events",
                 "n_tokens", "max_tokens", "finish", "status", "error",
                 "tokens", "plen", "ended")

    def __init__(self, due, max_tokens, plen):
        self.due, self.max_tokens, self.plen = due, max_tokens, plen
        self.sent = self.t_first = self.t_last = None
        self.events = []  # (time, tokens in the SSE event)
        self.n_tokens, self.finish, self.status, self.error = 0, None, 0, None
        self.tokens = []
        self.ended = None  # when the client saw the request's end

    @property
    def ok(self) -> bool:
        return (self.status == 200 and self.error is None
                and self.finish == "length"
                and self.n_tokens == self.max_tokens)


async def stream_one(session, url: str, body: dict, rec: Record,
                     keep_tokens: bool = False) -> Record:
    rec.sent = time.monotonic()
    try:
        async with session.post(url, json=body) as resp:
            rec.status = resp.status
            if resp.status != 200:
                rec.error = (await resp.text())[:200]
                return rec
            async for raw in resp.content:
                if not raw.startswith(b"data: "):
                    continue
                now = time.monotonic()
                data = raw[6:].strip()
                if data == b"[DONE]":
                    break
                doc = json.loads(data)
                if "error" in doc:
                    rec.error = json.dumps(doc["error"])[:200]
                    continue
                toks = doc.get("token_ids") or []
                if toks:
                    if rec.t_first is None:
                        rec.t_first = now
                    rec.t_last = now
                    rec.n_tokens += len(toks)
                    rec.events.append((now, len(toks)))
                    if keep_tokens:
                        rec.tokens += toks
                rec.finish = doc["choices"][0]["finish_reason"] or rec.finish
    except asyncio.CancelledError:
        rec.error = rec.error or "cut at the end of the run"
        raise
    except Exception as e:  # noqa: BLE001 - a failed request is a result
        rec.error = f"{type(e).__name__}: {e}"[:200]
    return rec


#: Longest silence a client accepts on a stream inside the mix, and in the
#: warm-up. In a checkout with an empty compile cache the first lone request
#: builds its prefill and up to four chunk programs before its first token is
#: read back: over 120 s on the v5e (my chip run, PR 24, the check's first
#: run), so the warm-up waits as long as a first run may take.
READ_TIMEOUT_S = 120
WARMUP_READ_TIMEOUT_S = 1000


def make_session(read_timeout_s: float = READ_TIMEOUT_S):
    import aiohttp

    return aiohttp.ClientSession(
        connector=aiohttp.TCPConnector(limit=0),
        timeout=aiohttp.ClientTimeout(total=None, sock_read=read_timeout_s))


async def lone_requests(url: str, bodies: list[dict], copies: int):
    """Warm-up: each body sent alone (with several replicas, `copies` at
    once, so that each replica tends to get one), one after the other."""
    out = []
    async with make_session(WARMUP_READ_TIMEOUT_S) as session:
        for body in bodies:
            recs = [Record(None, body["max_tokens"], len(body["prompt"]))
                    for _ in range(copies)]
            t = time.monotonic()
            await asyncio.gather(*(stream_one(session, url, body, r, True)
                                   for r in recs))
            say(f"warm-up: {len(body['prompt'])} prompt tokens, "
                f"{body['max_tokens']} answer tokens, temperature "
                f"{body['temperature']:g}: {time.monotonic() - t:.1f}s")
            out.append(recs)
    return out


async def offer(url: str, reqs, tr: dict, t_sched0: float, t0: float,
                t1: float, on_window=None) -> tuple[list[Record], list]:
    """Offer the mix from t_sched0; the window is [t0, t1). Returns every
    record made and the window's records (due, or sent, inside it). The same
    traffic goes on after t1 until the window's requests have ended or
    `drain_s` has passed, so that the last of them see the load the first
    ones saw."""
    records: list[Record] = []
    tasks: set = set()
    is_open = tr["loop"] == "open"
    t_stop = t1 + float(tr.get("drain_s", 20))

    def in_window(r: Record) -> bool:
        at = r.due if is_open else r.sent
        return at is not None and t0 <= at < t1

    def window_done() -> bool:
        return time.monotonic() >= t1 and all(
            r.ended is not None for r in records if in_window(r))

    async with make_session() as session:
        async def one(req, due):
            rec = Record(due, req["body"]["max_tokens"],
                         len(req["body"]["prompt"]))
            records.append(rec)
            await stream_one(session, url, req["body"], rec)
            rec.ended = time.monotonic()

        async def closed_client():
            while time.monotonic() < t_stop and not window_done():
                await one(next(reqs), None)

        async def open_arrivals():
            for req in reqs:
                due = t_sched0 + req["due_s"]
                if due >= t_stop or (due >= t1 and window_done()):
                    return
                delay = due - time.monotonic()
                if delay > 0:
                    await asyncio.sleep(delay)
                t = asyncio.ensure_future(one(req, due))
                tasks.add(t)
                t.add_done_callback(tasks.discard)

        if is_open:
            drivers = [asyncio.ensure_future(open_arrivals())]
        else:
            drivers = [asyncio.ensure_future(closed_client())
                       for _ in range(int(tr["clients"]))]
        side = (asyncio.ensure_future(on_window()) if on_window else None)
        while time.monotonic() < t_stop and not window_done():
            await asyncio.sleep(0.05)
        for t in drivers + list(tasks):
            t.cancel()
        await asyncio.gather(*drivers, *tasks, return_exceptions=True)
        if side is not None:
            await side
    return records, [r for r in records if in_window(r)]


# ----------------------------------------------------------- end to end
def end_to_end(window: list[Record], all_records: list[Record], tr: dict,
               t0: float, t1: float) -> tuple[dict, dict]:
    """(metrics by name, the printed detail). Every metric a cell might
    report is computed where its sample exists; the manifest says which of
    them the cell reports."""
    is_open = tr["loop"] == "open"
    done = [r for r in window if r.ok]
    n_missed = len(window) - len(done)
    out, detail = {}, {"attempted": len(window), "failed": n_missed}
    if is_open:
        ttft = stats.with_missed(
            [(r.t_first - r.due) * 1000.0 for r in done], n_missed)
    else:
        ttft = [(r.t_first - r.sent) * 1000.0 for r in done]
    tpot = [v for v in (stats.tpot_ms(r.t_first, r.t_last, r.n_tokens)
                        for r in done) if v is not None]
    if is_open:
        tpot = stats.with_missed(tpot, n_missed)
    if ttft:
        detail["ttft_ms"] = stats.summary(ttft)
    if tpot:
        detail["tpot_ms"] = stats.summary(tpot)
        out["tpot_p95_ms"] = stats.percentile(tpot, 95)
    if is_open and window:
        # From the instant a request was due to its last token, over its
        # tokens: queueing, admission and decoding together, as the pace a
        # caller of the whole answer sees. The plain latency is printed; it
        # follows the answers' lengths more than the system.
        latency = stats.with_missed(
            [(r.t_last - r.due) * 1000.0 for r in done], n_missed)
        per_token = stats.with_missed(
            [(r.t_last - r.due) * 1000.0 / r.n_tokens for r in done],
            n_missed)
        detail["latency_ms"] = stats.summary(latency)
        detail["latency_per_token_ms"] = stats.summary(per_token)
        out["latency_per_token_p50_ms"] = stats.percentile(per_token, 50)
    # All the tokens that reached a client inside the window, whichever
    # request they belong to, over the whole window.
    in_window = sum(n for r in all_records for t, n in r.events
                    if t0 <= t < t1)
    out["out_tok_s"] = in_window / (t1 - t0)
    detail["out_tok_s_by_completed_requests"] = sum(
        r.n_tokens for r in all_records
        if r.ok and t0 <= r.t_last < t1) / (t1 - t0)
    detail["tokens_in_window"] = in_window
    if is_open:
        late = [(r.sent - r.due) * 1000.0 for r in window
                if r.sent is not None]
        if late:
            detail["loadgen_late_ms"] = stats.summary(late)
    return out, detail


# ---------------------------------------------------------------- tracing
def state_call(method: str, timeout: float = 60.0, **kw):
    """One call to the cluster's controller, as `ray_tpu.util.state` makes
    them, with a time limit of the caller's (a profile takes its window)."""
    from ray_tpu._private.worker import global_worker

    w = global_worker()
    return w.io.run(w.controller.call(method, **kw), timeout=timeout)


def completion_spans(wall0: float = 0.0, wall1: float = math.inf) -> list:
    """The program's spans (RT_TRACING=1) of the completion requests whose
    traces overlap [wall0, wall1], and the engine's spans bound to them,
    from the controller's index. With tracing on every poll of the serve
    controller is a trace of its own, so traces are picked by their root's
    name."""
    spans = []
    for row in state_call("list_traces", limit=1_000_000)["traces"]:
        if not (row.get("name") or "").startswith("http POST"):
            continue
        if row["end"] < wall0 or row["start"] > wall1:
            continue
        doc = state_call("get_trace", trace_id=row["trace_id"])
        spans += [s for s in doc.get("spans", [])
                  if s["k"] in ("request", "engine")]
    return spans


def replica_workers(spans: list[dict]) -> dict[int, str]:
    """pid -> worker id of every process that recorded an engine span."""
    return {s["pid"]: s["w"] for s in spans if s["k"] == "engine"}


def profile_replica(worker_id: str, seconds: float, out_dir: str) -> dict:
    """A device trace of one replica through the runtime's own
    `profile_worker` RPC (only the process that holds the chip can trace
    it), unpacked under out_dir and reduced by a child held to the CPU."""
    rep = state_call("profile_worker", timeout=seconds + 120.0,
                     worker_id=worker_id, seconds=seconds, mode="jax")
    if not rep.get("found"):
        raise RunFailed(f"profile_worker: {rep.get('error')}")
    archive = rep["profile"]["archive_path"]
    os.makedirs(out_dir, exist_ok=True)
    with zipfile.ZipFile(archive) as z:
        z.extractall(out_dir)
    traces = [os.path.join(d, f) for d, _s, fs in os.walk(out_dir)
              for f in fs if f.endswith(".xplane.pb")]
    if not traces:
        raise RunFailed(f"no .xplane.pb in the profile archive {archive}")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "trace_reduce.py"),
         traces[0]], env=env, capture_output=True, text=True, timeout=300)
    if proc.returncode != 0:
        raise RunFailed(f"trace_reduce failed: {proc.stderr[-2000:]}")
    reduced = json.loads(proc.stdout.strip().splitlines()[-1])
    reduced["bytes"] = os.path.getsize(traces[0])
    return reduced


def hbm_peak() -> int:
    """Peak device memory of the fullest worker, from the program's own
    telemetry (`device.memory_stats()["peak_bytes_in_use"]`, sampled inside
    each worker that holds a device)."""
    rep = state_call("cluster_utilization")
    peaks = [int(w.get("hbm_peak", 0))
             for n in rep.get("nodes", {}).values()
             for w in (n.get("workers") or {}).values()]
    return max(peaks, default=0)


# --------------------------------------------------------------- reference
class ReferenceProbe:
    """Runs in a worker that holds one chip, after the replicas are gone."""

    def check(self, reference_path: str, llm: dict, cases: list) -> dict:
        return manifest.load_module(reference_path).check(llm, cases)


def run_reference(config: dict, cases: list, timeout: float) -> dict:
    import ray_tpu

    opts = {"num_cpus": 0}
    if config.get("platform", "tpu") == "tpu":
        opts["num_tpus"] = 1
    probe = ray_tpu.remote(**opts)(ReferenceProbe).remote()
    try:
        return ray_tpu.get(probe.check.remote(
            config["reference"], config["llm_config"], cases),
            timeout=timeout)
    finally:
        ray_tpu.kill(probe)


# -------------------------------------------------------------------- run
class Served:
    """A deployment that is up and warm: where it listens, how many
    replicas, the set-up split so far, the greedy (prompt, tokens) pairs
    that were served alone for the reference."""

    def __init__(self, base, n_rep, split, greedy, session_dir):
        self.base, self.url = base, f"{base}/v1/completions"
        self.n_rep, self.split, self.greedy = n_rep, split, greedy
        self.session_dir = session_dir


def start(cell: dict, trace: bool) -> Served:
    """Cluster, deployment, device checks, lone warm-up requests. The
    caller owns the shutdown (`serve.shutdown()`, `ray_tpu.shutdown()`),
    also when this raises."""
    config, tr = cell["config"], cell["traffic"]
    chips = int(cell["cell"]["chips"])
    n_rep = int(config["app_kwargs"].get("num_replicas", 1))
    vocab = int(config["llm_config"]["vocab_size"])
    on_tpu = config.get("platform", "tpu") == "tpu"
    preflight(config, chips)
    session_dir = place_state()
    if trace:
        os.environ["RT_TRACING"] = "1"
        os.environ["RT_TRACE_MAX_TRACES"] = "1000000"
        os.environ["RT_TRACE_BUFFER_SPANS"] = "262144"

    import ray_tpu
    from ray_tpu import serve

    split = {}
    t = time.monotonic()
    ray_tpu.init(**({} if on_tpu else {"num_cpus": max(4, 2 * n_rep)}))
    split["cluster_s"] = time.monotonic() - t
    if on_tpu:
        have = ray_tpu.cluster_resources().get("TPU", 0)
        if have < chips:
            raise RunFailed(f"the cluster advertises TPU={have:g}, the "
                            f"cell needs {chips}")
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    t = time.monotonic()
    serve.run(build_app(config), port=port, timeout_s=600)
    split["deploy_s"] = time.monotonic() - t
    st0 = replica_stats(base, n_rep)
    split["runtime_init_s"] = max(s["runtime_init_s"] for s in st0)
    split["engine_init_s"] = max(s["engine_init_s"] for s in st0)
    for s in st0:
        say(f"replica pid={s['pid']} platform={s['platform']} "
            f"kind={s['device_kind']!r} holds {s['chip_files_open']}")
    problems = check_devices(st0, config)
    if problems:
        raise RunFailed("; ".join(problems))

    # Lone warm-up requests: every program the mix can reach. With several
    # replicas, waves of one copy per replica until every replica has built
    # the same number of programs and a further wave builds none.
    warm = traffic_mod.warmup_bodies(tr, vocab)
    served = Served(base, n_rep, split, [], session_dir)
    t = time.monotonic()
    waves = 0
    while True:
        before = sum(s["compile_count"] for s in replica_stats(base, n_rep))
        recs = asyncio.run(lone_requests(
            served.url, [w["body"] for w in warm], n_rep))
        waves += 1
        for w, group in zip(warm, recs):
            for r in group:
                if not r.ok:
                    raise RunFailed(
                        f"warm-up request failed: status {r.status} "
                        f"{r.error} {r.n_tokens}/{r.max_tokens} tokens")
            if w["check"] and waves == 1:
                served.greedy.append((w["body"]["prompt"], group[0].tokens))
        now = replica_stats(base, n_rep)
        built = sum(s["compile_count"] for s in now) - before
        even = len({s["compile_count"] for s in now}) == 1
        if n_rep == 1 or (built == 0 and even) or waves >= 6:
            break
    split["warmup_s"] = time.monotonic() - t
    split["warmup_waves"] = waves
    split["compile_s"] = max(s["compile_s"] for s in now)
    split["programs_built"] = sum(s["compile_count"] for s in now)
    return served


def run(cell: dict, args) -> dict:
    config, tr = cell["config"], cell["traffic"]
    vocab = int(config["llm_config"]["vocab_size"])
    seconds = float(args.seconds)
    trace = bool(args.trace)

    import ray_tpu
    from ray_tpu import serve

    problems: list[str] = []
    result = None
    try:
        served = start(cell, trace)
        base, url, n_rep = served.base, served.url, served.n_rep
        split, greedy, session_dir = (served.split, served.greedy,
                                      served.session_dir)
        workers = find_replica_workers() if trace else {}

        # The mix itself: preload, window, drain.
        preload = float(tr.get("preload_s", 8))
        reqs = traffic_mod.requests(tr, vocab, int(args.seed))
        t_sched0 = time.monotonic() + 0.2
        t0 = t_sched0 + preload
        t1 = t0 + seconds
        wall_off = time.time() - time.monotonic()
        marks: dict = {}

        async def on_window():
            """Reads taken at the window's edges, beside the load."""
            loop = asyncio.get_running_loop()
            await asyncio.sleep(max(0.0, t0 - time.monotonic()))
            marks["stats0"] = await loop.run_in_executor(
                None, replica_stats, base, n_rep)
            if trace:
                # The device is recorded in the window's last second; the
                # capture then takes half a minute to bring back, in which
                # the replica's host threads stall. That falls into the
                # drain: taken mid-window it spoils an open loop's every
                # later request (a traced run read a median time to first
                # token of 4.4 s against 0.4 s untraced).
                await asyncio.sleep(max(0.0, t1 - PROFILE_SECONDS - 0.5
                                        - time.monotonic()))
                marks["profile"] = await loop.run_in_executor(
                    None, take_profile, workers, session_dir)
            await asyncio.sleep(max(0.0, t1 - time.monotonic()))
            marks["stats1"] = await loop.run_in_executor(
                None, replica_stats, base, n_rep)

        say(f"offering {cell['cell']['traffic']} "
            f"({tr['loop']} loop) for {preload:g}s before the window")
        records, window = asyncio.run(offer(
            url, reqs, tr, t_sched0, t0, t1, on_window))
        setup_s = t0 - T_PROCESS_START
        say(f"window of {seconds:g}s done; drained in "
            f"{time.monotonic() - t1:.1f}s")

        metrics, detail = end_to_end(window, records, tr, t0, t1)
        metrics["setup_s"] = setup_s
        built_in_window = (
            sum(s["compile_count"] for s in marks["stats1"])
            - sum(s["compile_count"] for s in marks["stats0"]))
        built_in_preload = (sum(s["compile_count"] for s in marks["stats0"])
                            - split["programs_built"])
        detail.update(programs_built_in_window=built_in_window,
                      programs_built_in_preload=built_in_preload,
                      served_per_replica=[s["served"]
                                          for s in marks["stats1"]],
                      shed_total=shed_total(marks["stats1"]))
        bad = [r for r in window if r.status == 200 and not r.error
               and r.finish is not None and not r.ok]
        if bad:
            problems.append(
                f"{len(bad)} completed requests do not have exactly their "
                f"max_tokens with finish reason 'length'")
        if built_in_window:
            problems.append(f"{built_in_window} programs were built inside "
                            f"the measured window")
        problems += check_devices(marks["stats1"], config)
        peak = hbm_peak()
        spans = []
        if trace:
            time.sleep(FLUSH_WAIT_S)
            spans = completion_spans(t0 + wall_off, t1 + wall_off)
        say("set-up split: " + json.dumps(
            {k: round(v, 3) if isinstance(v, float) else v
             for k, v in split.items()}))
        say("detail: " + json.dumps(detail))

        t = time.monotonic()
        serve.shutdown()
        say(f"serve.shutdown() in {time.monotonic() - t:.1f}s")
        t = time.monotonic()
        ref = run_reference(config, greedy, timeout=300)
        worst = max((r["max_gap"] for r in ref["rows"]), default=math.inf)
        say(f"reference on {ref['platform']} {ref['device_kind']!r} in "
            f"{time.monotonic() - t:.1f}s (its own work "
            f"{ref['seconds']:.1f}s): worst logit gap {worst:.4f} against a "
            f"tolerance of {ref['tolerance']}; rows {json.dumps(ref['rows'])}")
        if not ref["rows"] or worst > ref["tolerance"] or not all(
                r["finite"] for r in ref["rows"]):
            problems.append(f"served greedy tokens disagree with the plain "
                            f"reference: worst gap {worst}")
        if ref["platform"] != config.get("platform", "tpu"):
            problems.append(f"the reference ran on {ref['platform']!r}")

        st = marks["stats1"]
        device = {"platform": st[0]["platform"],
                  "kind": st[0]["device_kind"],
                  "count": sum(len(s["device_ids"]) for s in st),
                  "memory_peak_bytes": peak}
        result = {"correct": not problems,
                  "attempted": detail["attempted"],
                  "failed": detail["failed"], "device": device,
                  "e2e": metrics, "detail": detail, "split": split,
                  "spans": spans, "profile": marks.get("profile"),
                  "window_wall": (t0 + wall_off, t1 + wall_off),
                  "records": window, "problems": problems}
    finally:
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001 - already down, or never up
            pass
        ray_tpu.shutdown()
    if "jax" in sys.modules:
        raise RunFailed("the benchmark's own process imported jax")
    return result


def find_replica_workers() -> dict[int, str]:
    """pid -> worker id of the replicas, from the engine spans that the
    warm-up requests left with the controller."""
    time.sleep(FLUSH_WAIT_S)
    return replica_workers(completion_spans())


#: Length of the device trace. The runtime's `profile_worker` RPC allows a
#: capture `seconds + 30` at the node agent; on the v5e a window of 1.5 s
#: took 34.6 s to capture and bring back (33 MB) and one of 3 s timed out,
#: so 1 s is what fits with room to spare (PERF.md, PR 24).
PROFILE_SECONDS = 1.0


def take_profile(workers: dict[int, str],
                 session_dir: str) -> dict | None:
    """Trace one replica (the lowest pid) inside the window."""
    if not workers:
        say("no engine span from the warm-up: no replica to profile")
        return None
    pid = sorted(workers)[0]
    length = PROFILE_SECONDS
    out_dir = os.path.join(session_dir, f"profile_{os.getpid()}")
    t = time.monotonic()
    try:
        reduced = profile_replica(workers[pid], length, out_dir)
    except Exception as e:  # noqa: BLE001 - the run goes on without a trace
        say(f"no device trace: {type(e).__name__}: {e} "
            f"(after {time.monotonic() - t:.1f}s)")
        return None
    reduced["replica_pid"] = pid
    reduced["replicas_traced"] = 1
    say(f"profiled replica pid={pid} for {length:g}s "
        f"(took {time.monotonic() - t:.1f}s, trace "
        f"{reduced['bytes'] / 1e6:.1f} MB)")
    return reduced
