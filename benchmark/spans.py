"""Helpers the per-layer readers share for reading the program's spans
(`ray_tpu/_private/tracing.py`, RT_TRACING=1). A span is a dict with the
program's compact keys: t(race), s(pan), p(arent), n(ame), k(ind), a/b
(start/end, wall seconds), pid, at(tributes)."""

from __future__ import annotations


def named(spans: list[dict], name: str, lo: float, hi: float) -> list[dict]:
    """Spans of one name that START inside [lo, hi) (wall seconds)."""
    return [s for s in spans if s["n"] == name and lo <= s["a"] < hi]


def request_roots(spans: list[dict]) -> dict[str, dict]:
    """trace id -> the proxy's root span of a completion request."""
    return {s["t"]: s for s in spans
            if s["k"] == "request" and s["p"] is None
            and s["n"].startswith("http POST")}


def traced_window(run: dict) -> tuple[float, float] | None:
    """The profiler's window in wall seconds, from the trace's own record
    of when it started and stopped."""
    prof = run.get("profile")
    if not prof or not prof.get("devices") or not prof["profile_start_ns"]:
        return None
    return prof["profile_start_ns"] / 1e9, prof["profile_stop_ns"] / 1e9


def program_seconds(run: dict, names: tuple[str, ...]) -> float | None:
    """Device seconds of the named jitted programs in the traced window,
    averaged over the devices traced."""
    prof = run.get("profile")
    if not prof or not prof.get("devices"):
        return None
    per_dev = [sum(secs for prog, secs in d["programs"].items()
                   if prog in names) for d in prof["devices"]]
    return sum(per_dev) / len(per_dev)


def decode_steps(run: dict):
    """(steps, device seconds) of the `jit_chunk` programs in the traced
    window, both from the device trace: the seconds of their executions and
    the loop steps those executions made. None without a trace."""
    prof = run.get("profile")
    if not prof or not prof.get("devices"):
        return None
    secs = sum(d["programs"].get("jit_chunk", 0.0) for d in prof["devices"])
    steps = sum(d["loop_steps"].get("jit_chunk", 0) for d in prof["devices"])
    return (steps, secs) if steps and secs else None


def traced_chunks(run: dict) -> list[dict]:
    """The `engine.dispatch_chunk` spans the traced replica dispatched
    while the profiler ran (wall time; a chunk runs a little after it is
    dispatched, which matters little to a mean over them)."""
    window = traced_window(run)
    if window is None:
        return []
    pid = run["profile"].get("replica_pid")
    return [c for c in named(run["spans"], "engine.dispatch_chunk", *window)
            if pid is None or c["pid"] == pid]
