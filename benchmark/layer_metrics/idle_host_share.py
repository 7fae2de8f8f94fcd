"""idle_host_share — layer: device (the profiler trace; the host's phases on
its clock).

Of the seconds the traced device stood idle between its first and its last
operation, the share, in %, that lies inside a phase of the host's own work:
the scheduler's `engine.admit`, `engine.dispatch` and `engine.deliver`, or
the prefill lane's `engine.prefill_dispatch` (`TraceAnnotation`s of
llm/engine.py, on the device's clock). The rest lies inside `engine.sync`
(the host waits for the device, which has nothing queued), inside
`engine.idle_wait` (nothing to do) or inside no phase. Printed beside it:
the idle seconds by phase, and for each of the ten longest gaps the phase
that covers most of it. Nothing where the trace has no `engine.*` event."""

from benchmark import engine_spans as es, host_trace


@es.never_raises
def read(run: dict):
    dev = host_trace.device(run)
    if not dev or "idle_by_phase_s" not in dev or dev["idle_s"] <= 0:
        return None
    got = host_trace.host_phases(run)
    by = dev["idle_by_phase_s"]
    idle = dev["idle_s"]
    print(f"idle_host_share: device idle {idle:.4f}s of "
          f"{(dev['last_ns'] - dev['first_ns']) / 1e9:.4f}s; by scheduler "
          f"phase: " + ", ".join(f"{k} {v:.4f}" for k, v in by.items())
          + f"; inside engine.prefill_dispatch (its own thread) "
          f"{dev['idle_in_prefill_dispatch_s']:.4f}; attributed to a phase "
          f"{100 * (idle - by['none']) / idle:.1f}%", flush=True)
    print("idle_host_share: host events in the trace: " + ", ".join(
        f"{k} x{v['events']} {v['seconds']:.4f}s"
        for k, v in got["host"].items()), flush=True)
    for start, secs, phase, covered in dev["gaps"]:
        print(f"idle_host_share: gap of {secs * 1e3:.3f} ms at "
              f"+{(start - dev['first_ns']) / 1e9:.3f}s: {phase} "
              f"({covered * 1e3:.3f} ms of it)", flush=True)
    prof = run.get("profile") or {}
    if got.get("wall_offset_ns") and prof.get("profile_start_ns"):
        print(f"idle_host_share: the trace's clock starts "
              f"{(got['wall_offset_ns'] - prof['profile_start_ns']) / 1e6:.3f}"
              f" ms after the profiler's own start stamp (from the wall_ns "
              f"of engine.dispatch)", flush=True)
    return 100.0 * dev["idle_in_host_work_s"] / idle
