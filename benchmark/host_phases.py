"""From the same profiler trace (`*.xplane.pb`) as `trace_reduce.py`, what
that one skips: the host plane. Run as a program of its own, held to the
CPU, because reading a trace imports JAX and the process that runs the
benchmark never does:

    JAX_PLATFORMS=cpu python benchmark/host_phases.py <file.xplane.pb>

prints one JSON object. `reduce_host` works on plain data, so it is tested
on a small recorded trace kept as JSON under tests/data/ without JAX.

The program (llm/engine.py, with RT_TRACING=1) enters a
`jax.profiler.TraceAnnotation` around each phase of a pass of its scheduler
loop — `engine.admit`, `engine.dispatch`, `engine.sync`, `engine.deliver`,
`engine.idle_wait` — and, on the prefill lane's thread, around
`engine.prefill_dispatch`. Inside a profiler session these are events of the
`/host:CPU` plane on the clock of the `/device:TPU:n` planes.

What is taken:
  host        per `engine.*` name: how many events, their seconds
  wall_offset_ns   median over the `engine.dispatch` events of (their
              `wall_ns` stat - their start): trace clock -> wall clock
  devices     per device plane: the idle seconds between its first and last
              operation; of these, the seconds inside each scheduler phase
              (the phases of one thread do not overlap; `none` is what no
              phase covers), inside `engine.prefill_dispatch` (another
              thread, so it overlaps the phases) and inside the union of
              the host's own work (admit, dispatch, deliver,
              prefill_dispatch); the ten longest gaps, each with the phase
              that covers most of it
A trace without `engine.*` events (a program that writes none) gives
`"host": {}` and no attribution.
"""

from __future__ import annotations

import bisect
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_reduce as tr  # noqa: E402

#: The phases of the scheduler's thread, in the order of a pass.
SCHED_PHASES = ("admit", "dispatch", "sync", "deliver", "idle_wait")
#: The host's own work: while the host is here, the device gets nothing new.
HOST_WORK = ("admit", "dispatch", "deliver", "prefill_dispatch")
TOP = 10


def merged(intervals) -> list[tuple[int, int]]:
    """Sorted, disjoint (start, end) covering the same instants."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap_ns(lo: int, hi: int, ivs: list[tuple[int, int]],
               starts: list[int]) -> int:
    """Nanoseconds of [lo, hi) inside the merged intervals `ivs`."""
    total = 0
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    while i < len(ivs) and ivs[i][0] < hi:
        total += max(0, min(hi, ivs[i][1]) - max(lo, ivs[i][0]))
        i += 1
    return total


def reduce_host(planes: list[dict]) -> dict:
    """planes: [{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns, wall_ns], ...]}]}]; `wall_ns` is the stat of an `engine.dispatch`
    event and may be missing."""
    phases: dict[str, list[tuple[int, int]]] = {}
    offsets = []
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for ev in line["events"]:
                name, s, d = ev[0], int(ev[1]), int(ev[2])
                if not name.startswith("engine."):
                    continue
                phases.setdefault(name[len("engine."):], []).append((s, s + d))
                if name == "engine.dispatch" and len(ev) > 3 and ev[3]:
                    offsets.append(int(ev[3]) - s)
    host = {name: {"events": len(ivs),
                   "seconds": sum(e - s for s, e in ivs) / 1e9}
            for name, ivs in sorted(phases.items())}
    cover = {name: merged(ivs) for name, ivs in phases.items()}
    cover["host_work"] = merged(iv for name in HOST_WORK
                                for iv in phases.get(name, []))
    cover["any"] = merged(iv for name in SCHED_PHASES
                          for iv in phases.get(name, []))
    starts = {name: [s for s, _e in ivs] for name, ivs in cover.items()}

    devices = []
    for plane in planes:
        if not plane["name"].startswith("/device:TPU:"):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = lines.get(tr.OPS_LINE) or []
        modules = lines.get(tr.MODULES_LINE) or []
        busy = [(int(e[1]), int(e[1]) + int(e[2])) for e in ops or modules]
        if not busy:
            continue
        lo, hi = min(s for s, _e in busy), max(e for _s, e in busy)
        idle = tr.gaps(busy, lo, hi)
        dev = {"plane": plane["name"], "first_ns": lo, "last_ns": hi,
               "idle_s": sum(d for _s, d in idle) / 1e9}
        if phases:
            by_phase = {name: 0 for name in SCHED_PHASES + ("none",)}
            beside = {"prefill_dispatch": 0, "host_work": 0}
            longest = []
            for s, d in idle:
                inside = {name: overlap_ns(s, s + d, cover.get(name, []),
                                           starts.get(name, []))
                          for name in SCHED_PHASES + tuple(beside)}
                inside["none"] = d - overlap_ns(s, s + d, cover["any"],
                                                starts["any"])
                for name in by_phase:
                    by_phase[name] += inside[name]
                for name in beside:
                    beside[name] += inside[name]
                most = max(by_phase, key=lambda name: inside[name])
                longest.append([s, d / 1e9, most, inside[most] / 1e9])
            dev["idle_by_phase_s"] = {k: v / 1e9 for k, v in by_phase.items()}
            dev["idle_in_prefill_dispatch_s"] = beside["prefill_dispatch"] / 1e9
            dev["idle_in_host_work_s"] = beside["host_work"] / 1e9
            dev["gaps"] = sorted(longest, key=lambda g: -g[1])[:TOP]
        devices.append(dev)
    offsets.sort()
    return {"host": host, "devices": devices,
            "wall_offset_ns": offsets[len(offsets) // 2] if offsets else None}


def read_xplane(path: str) -> list[dict]:
    """The host planes' `engine.*` events and the device planes' operation
    events (their modules' where a plane has no operations line), as plain
    data."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_host = plane.name.startswith("/host:")
        if not (is_host or plane.name.startswith("/device:TPU:")):
            continue
        lines = []
        for ln in plane.lines:
            if is_host:
                events = []
                for ev in ln.events:
                    if not ev.name.startswith("engine."):
                        continue
                    wall = (dict(ev.stats).get("wall_ns")
                            if ev.name == "engine.dispatch" else None)
                    events.append([ev.name, int(ev.start_ns),
                                   int(ev.duration_ns), wall])
            elif ln.name in (tr.OPS_LINE, tr.MODULES_LINE):
                # the intervals are all that is read: names stay out
                events = [["", int(ev.start_ns), int(ev.duration_ns)]
                          for ev in ln.events]
            else:
                continue
            if events:
                lines.append({"name": ln.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def describe(path: str, per_line: int = 3) -> dict:
    """For reading a trace by hand: the first few events of every line of
    every plane with ALL their stats (`trace_reduce.py --describe` shows
    names and counts; this shows what an event carries)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = []
        for ln in plane.lines:
            evs, n = [], 0
            for ev in ln.events:
                n += 1
                if len(evs) < per_line or (ev.name.startswith("engine.")
                                           and len(evs) < 4 * per_line):
                    evs.append({"name": ev.name[:160],
                                "start_ns": int(ev.start_ns),
                                "duration_ns": int(ev.duration_ns),
                                "stats": {k: str(v)[:300]
                                          for k, v in ev.stats}})
            lines.append({"line": ln.name, "events": n, "first": evs})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


def main(argv: list[str]) -> int:
    if len(argv) >= 2 and argv[0] == "--describe":
        print(json.dumps(describe(argv[1])))
        return 0
    if len(argv) >= 3 and argv[0] == "--sample":
        # A slice of a trace as plain data, small enough to keep with the
        # tests: the events that start in its first argv[1] seconds, a
        # device's operations merged into the intervals it was busy in
        # (which is all `reduce_host` reads of them).
        planes = read_xplane(argv[2])
        first = min(ev[1] for p in planes for ln in p["lines"]
                    for ev in ln["events"])
        upto = first + int(float(argv[1]) * 1e9)
        for p in planes:
            for ln in p["lines"]:
                ln["events"] = [ev for ev in ln["events"] if ev[1] < upto]
                if not p["name"].startswith("/host:"):
                    ln["events"] = [["", s, e - s] for s, e in merged(
                        (ev[1], ev[1] + ev[2]) for ev in ln["events"])]
        print(json.dumps({"planes": planes}))
        return 0
    print(json.dumps(reduce_host(read_xplane(argv[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
