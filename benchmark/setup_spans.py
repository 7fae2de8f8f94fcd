"""What the readers of a replica's set-up share. With RT_TRACING=1 a process
that serves an engine writes its set-up account (`ray_tpu/_private/
telemetry.py` `SetupAccount`; the README's "Tracing & timeline") to
`<RT_SESSION_DIR>/setup/<pid>.json`: its STAGES (`replica.start`, and inside
it `runtime.init`, `engine.init`, ...: name `n`, wall start `a` and end `b`)
and one record per program BUILD (`fun_name`, wall `a` to `b`, `trace_s`,
`lower_s`, `compile_s`, `cache`, `retrieval_s`; for a build inside a call of
the engine's also `call_a`, the call's start, shared by the builds of one
call, `call_s` and, where the call's result was read, `ready_s`). The driver
sets RT_SESSION_DIR in this process's environment (`place_state`) and the
readers run in it after the cluster is down, so the file is how they reach
set-up: `completion_spans` hands them the requests' traces only.

The directory outlives a run, so a file counts only if its pid recorded an
`engine` span of this run and its process started after the benchmark did.
Everything is over what ENDED before the window's first instant. A program
that writes no such file (the parent of PR 56) gives every reader None."""

from __future__ import annotations

import json
import os

#: The programs a request can wait for, whose attention may be a Mosaic
#: kernel (`kernel`, told by the engine: `_prefill_form`, `_decode_form`).
SERVING = ("jit_chunk", "jit_prefill", "jit_place", "jit_sample1")


def accounts(run: dict) -> list[dict]:
    """The account of each of the run's replicas, cut to what ended before
    the window: `stages`, `builds`, `lo` (the window's first instant) and
    `bench_start` (the benchmark's own start on the wall clock)."""
    session = os.environ.get("RT_SESSION_DIR")
    if not session or not run.get("window_wall"):
        return []
    lo = run["window_wall"][0]
    bench_start = lo - run["e2e"]["setup_s"]
    pids = {s["pid"] for s in run.get("spans") or [] if s.get("k") == "engine"}
    try:
        names = sorted(os.listdir(os.path.join(session, "setup")))
    except OSError:
        return []
    found = []
    for name in names:
        if not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(session, "setup", name)) as f:
                doc = json.load(f)
            if doc["pid"] not in pids or doc["process_start"] < bench_start:
                continue  # another run's, or an earlier process of this pid
            found.append({
                "pid": doc["pid"], "lo": lo, "bench_start": bench_start,
                "stages": [s for s in doc["stages"] if s["b"] <= lo],
                "builds": [b for b in doc["builds"] if ended(b) <= lo]})
        except (OSError, ValueError, KeyError, TypeError):
            continue  # torn, or not an account: not this run's either way
    return found


def ended(build: dict) -> float:
    """When a build was over: its compile's end, or its call's result."""
    if "ready_s" in build:
        return max(build["b"], build["call_a"] + build["ready_s"])
    return build["b"]


def stage(acct: dict, name: str) -> dict | None:
    return next((s for s in acct["stages"] if s["n"] == name), None)


def stage_s(acct: dict, name: str) -> float | None:
    st = stage(acct, name)
    return None if st is None else st["b"] - st["a"]


def slowest(run: dict, read, pick=max):
    """`read(account)` of the replica that reads worst; None without an
    account, or where `read` finds nothing."""
    got = [v for v in map(read, accounts(run)) if v is not None]
    return pick(got) if got else None


# ------------------------------------------------------------- intervals
def union(spans) -> list[tuple]:
    """Disjoint sorted intervals covering what `spans` cover."""
    out: list[list] = []
    for a, b in sorted(s for s in spans if s[1] > s[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(s) for s in out]


def seconds(spans) -> float:
    return sum(b - a for a, b in union(spans))


def less(spans, holes) -> list[tuple]:
    """What `spans` cover and `holes` do not."""
    out = []
    holes = union(holes)
    for a, b in union(spans):
        for ha, hb in holes:
            if hb <= a or ha >= b:
                continue
            if ha > a:
                out.append((a, ha))
            a = max(a, hb)
        if a < b:
            out.append((a, b))
    return out


def build_spans(acct: dict, inside: tuple | None = None) -> list[tuple]:
    got = [(b["a"], b["b"]) for b in acct["builds"]]
    if inside:
        got = [(max(a, inside[0]), min(b, inside[1])) for a, b in got]
    return got


def calls(acct: dict) -> dict[float, dict]:
    """call_a -> {"span": (start, result on the host), "builds": [...]} of
    the engine's calls that built something and whose result was read."""
    out: dict[float, dict] = {}
    for b in acct["builds"]:
        if "ready_s" in b:
            c = out.setdefault(b["call_a"], {
                "span": (b["call_a"], b["call_a"] + b["ready_s"]),
                "builds": []})
            c["builds"].append(b)
    return out


# ---------------------------------------------------------- the six parts
def trace_lower_s(acct: dict) -> float:
    return sum(b["trace_s"] + b["lower_s"] for b in acct["builds"])


def compile_s(acct: dict) -> float:
    return sum(b["compile_s"] for b in acct["builds"])


def first_run_spans(acct: dict) -> list[tuple]:
    """The wall time in which a first call waited for its result and
    nothing was being built: each call's `ready_s - trace_s - lower_s -
    compile_s`, but a second counted once where calls lie inside one
    another (a prefill's first token is read behind the first chunk, whose
    program is built meanwhile on the scheduler's thread)."""
    return less([c["span"] for c in calls(acct).values()], build_spans(acct))


def engine_init_self_s(acct: dict) -> float | None:
    st = stage(acct, "engine.init")
    if st is None:
        return None
    return st["b"] - st["a"] - seconds(build_spans(acct, (st["a"], st["b"])))


def covered(acct: dict) -> tuple | None:
    """(begin of `replica.start`, the last build's end or read result
    before the window): the wall time the account covers."""
    root = stage(acct, "replica.start")
    if root is None:
        return None
    return root["a"], max([root["b"]] + [ended(b) for b in acct["builds"]])


def rest_s(acct: dict) -> float | None:
    """Of the covered wall time, what lies in no stage below `replica.start`,
    in no build and in no first call's wait: the warm-up requests' own
    prefill and decode, HTTP, the driver's polls between waves."""
    span = covered(acct)
    if span is None:
        return None
    told = build_spans(acct) + [c["span"] for c in calls(acct).values()] + [
        (s["a"], s["b"]) for s in acct["stages"]
        if s["n"] in ("runtime.init", "engine.init")]
    return seconds(less([span], told))


def build_gaps_s(acct: dict) -> float:
    """Inside the builds' own wall time, what is none of their three parts:
    between a trace's end and the lowering's start, a lowering's end and the
    compile's start (40 ms a build on the v5e's host; below zero where two
    threads built at once, which a sum of parts counts twice)."""
    return seconds(build_spans(acct)) - trace_lower_s(acct) - compile_s(acct)


def top(acct: dict, key, n: int = 5) -> str:
    """The `n` costliest program names by `key(build)`, with their builds."""
    by: dict[str, list] = {}
    for b in acct["builds"]:
        by.setdefault(b["fun_name"], []).append(key(b))
    rows = sorted(by.items(), key=lambda kv: -sum(kv[1]))[:n]
    return ", ".join(f"{name} x{len(v)} {sum(v):.2f}s" for name, v in rows)
