"""From a profiler trace (`*.xplane.pb`) to numbers. Run as a program of its
own, held to the CPU, because reading a trace imports JAX and the process
that runs the benchmark never does:

    JAX_PLATFORMS=cpu python benchmark/trace_reduce.py <file.xplane.pb>

prints one JSON object. `reduce_planes` works on plain data (lists of
(name, start_ns, duration_ns)), so it is tested on a small recorded trace
kept as JSON under tests/data/ without JAX.

What is taken, per device plane (`/device:TPU:n`):
  busy_s      the union of the intervals in which an operation ran, from the
              "XLA Ops" line (the "XLA Modules" line where there is none)
  programs    device seconds per jitted program, from the "XLA Modules"
              line, under the program's name without its fingerprint
  loop_steps  per program, the steps its executions made: inside one
              execution every operation of a loop's body appears once per
              iteration under one name, so the most often repeated name
              counts the iterations (1 for a program without a loop). For
              `jit_chunk` these are the decode steps, on the trace's own
              clock.
  ops         the operations that took most SELF time (an operation's
              duration less that of the operations nested in it: a `while`
              spans its body and would otherwise hide it), operations of
              one kind and result shape summed into one row
  gaps        the longest intervals in which nothing ran
`window_s` is the length of the device's own record, from its first
operation's start to its last one's end: the profiler's `profile_start_time`
and `profile_stop_time` (kept, in unix nanoseconds) also span the seconds it
takes to start and stop, in which the device is not recorded.
"""

from __future__ import annotations

import bisect
import json
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def union_seconds(intervals) -> float:
    """Total length of the union of (start_ns, end_ns) intervals, in s."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle intervals (start_ns, length_ns) between lo and hi."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, s - at))
        at = max(at, e)
    if hi > at:
        out.append((at, hi - at))
    return out


def self_times(events) -> dict[str, float]:
    """Self seconds per operation name. `events` are (name, start, dur) of
    one line, where nesting is by containment."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, end_ns, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _e, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + self_ns / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def loop_steps(module_events, op_events) -> dict[str, int]:
    """Steps per program: for each execution on the modules line, the
    highest number of times one operation name starts inside it."""
    ops = sorted((s, name) for name, s, _d in op_events)
    starts = [s for s, _n in ops]
    out: dict[str, int] = {}
    for name, s, d in module_events:
        lo, hi = bisect.bisect_left(starts, s), bisect.bisect_left(starts, s + d)
        counts: dict[str, int] = {}
        for _s, op in ops[lo:hi]:
            counts[op] = counts.get(op, 0) + 1
        p = program_name(name)
        out[p] = out.get(p, 0) + max(counts.values(), default=1)
    return out


def program_name(event_name: str) -> str:
    """`jit_chunk(1234567)` -> `jit_chunk`."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def op_label(hlo: str) -> str:
    """`%fusion.12 = bf16[8,32,96]{2,1,0:T(8,128)} fusion(...), kind=kLoop`
    -> `%fusion.12 fusion bf16[8,32,96]`: the name, the operation and the
    result's shape, without layouts, operands and attributes."""
    flat = re.sub(r"\{[^{}]*\}", "", hlo)
    m = re.match(r"(%[\w.\-]+) = (.*?) ([\w\-]+)\(", flat)
    if not m:
        return hlo[:100]
    shape = m.group(2) if len(m.group(2)) <= 48 else m.group(2)[:45] + "..."
    return f"{m.group(1)} {m.group(3)} {shape}"


def grouped_ops(self_seconds) -> list[list]:
    """[(label, seconds)], most time first, where operations of one kind
    and result shape are one row: the 16 layers of a model each have their
    own `%fusion.N` for the same work, and a list of single names would show
    ten of them and hide everything else."""
    groups: dict[str, list] = {}
    for name, secs in self_seconds:
        label = op_label(name)
        kind = label.split(" ", 1)[1] if label.startswith("%") else label
        g = groups.setdefault(kind, [0.0, 0])
        g[0] += secs
        g[1] += 1
    return [[f"{kind} (x{n} names)" if n > 1 else kind, secs]
            for kind, (secs, n) in sorted(groups.items(),
                                          key=lambda kv: -kv[1][0])]


def reduce_planes(planes: list[dict], start_ns: int, stop_ns: int) -> dict:
    """planes: [{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns], ...]}]}]. Event times are on the trace's own clock, which
    starts at the traced window's start."""
    devices = []
    for plane in planes:
        if not plane["name"].startswith("/device:TPU:"):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        if not ops:
            continue
        spans = [(s, s + d) for _n, s, d in ops]
        lo = min(s for s, _e in spans)
        hi = max(e for _s, e in spans)
        programs: dict[str, float] = {}
        runs: dict[str, int] = {}
        for name, _s, d in lines.get(MODULES_LINE, []):
            p = program_name(name)
            programs[p] = programs.get(p, 0.0) + d / 1e9
            runs[p] = runs.get(p, 0) + 1
        top_ops = sorted(self_times(ops).items(), key=lambda kv: -kv[1])
        longest = sorted(gaps(spans, lo, hi), key=lambda g: -g[1])[:TOP]
        devices.append({
            "plane": plane["name"], "busy_s": union_seconds(spans),
            "first_ns": lo, "last_ns": hi, "programs": programs,
            "program_runs": runs,
            "loop_steps": loop_steps(lines.get(MODULES_LINE, []),
                                     lines.get(OPS_LINE, [])),
            "ops": grouped_ops(top_ops)[:TOP],
            "gaps": [[s, d / 1e9] for s, d in longest]})
    extents = [(d["last_ns"] - d["first_ns"]) / 1e9 for d in devices]
    return {"profile_start_ns": start_ns, "profile_stop_ns": stop_ns,
            "window_s": max(extents, default=0.0), "devices": devices}


def read_xplane(path: str):
    """(planes as plain data, profile start, profile stop) of a trace."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes, start, stop = [], 0, 0
    for plane in data.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            start = int(stats.get("profile_start_time", 0))
            stop = int(stats.get("profile_stop_time", 0))
        if not plane.name.startswith("/device:"):
            continue
        planes.append({"name": plane.name, "lines": [
            {"name": ln.name,
             "events": [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ev in ln.events]}
            for ln in plane.lines]})
    return planes, start, stop


def describe(path: str) -> dict:
    """The shape of a trace, for reading one by hand: every plane, its
    lines, how many events each has, and its first few names."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = []
        for ln in plane.lines:
            evs = list(ln.events)
            names: dict[str, int] = {}
            for ev in evs:
                names[ev.name] = names.get(ev.name, 0) + 1
            lines.append({"line": ln.name, "events": len(evs),
                          "first": [[e.name, int(e.start_ns),
                                     int(e.duration_ns)] for e in evs[:3]],
                          "names": sorted(names.items(),
                                          key=lambda kv: -kv[1])[:12]})
        out.append({"plane": plane.name, "stats": [
            [k, str(v)] for k, v in plane.stats], "lines": lines})
    return {"planes": out}


def main(argv: list[str]) -> int:
    if len(argv) >= 2 and argv[0] == "--describe":
        print(json.dumps(describe(argv[1])))
        return 0
    if len(argv) >= 3 and argv[0] == "--sample":
        # A slice of a trace as plain data, small enough to keep with the
        # tests: the device events that start in its first argv[1] seconds.
        planes, start, stop = read_xplane(argv[2])
        first = min(ev[1] for p in planes for ln in p["lines"]
                    for ev in ln["events"])
        upto = first + int(float(argv[1]) * 1e9)
        for p in planes:
            for ln in p["lines"]:
                ln["events"] = [ev for ev in ln["events"] if ev[1] < upto]
        print(json.dumps({"planes": planes, "profile_start_ns": start,
                          "profile_stop_ns": stop}))
        return 0
    planes, start, stop = read_xplane(argv[0])
    print(json.dumps(reduce_planes(planes, start, stop)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
