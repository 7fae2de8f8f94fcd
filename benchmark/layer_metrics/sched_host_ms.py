"""sched_host_ms — layer: engine scheduler (llm/engine.py `_run_scheduler`).

Mean over the window's `engine.iteration` spans of `admit_ms + dispatch_ms +
deliver_ms`, in ms: the host's own work in one pass of the scheduler loop,
without the blocking read (`sync_ms`, mostly a wait for the device) and
without the wait for work (`idle_ms`). While the host does this work it
dispatches nothing further, so past the depth of the pipeline it is time
the device stands idle.

Printed beside it: the mean of each phase, the share of the window's wall
time spent in `sync_ms` and in `idle_ms` (per replica), and the same means
over the passes of the device trace's own second, which says whether the
traced second is typical of the window."""

from benchmark import engine_spans as es, spans as sp

PHASES = ("admit_ms", "dispatch_ms", "sync_ms", "deliver_ms", "idle_ms")


def means(its: list[dict]) -> dict:
    return {k: sum(s["at"][k] for s in its) / len(its) for k in PHASES}


def host_ms(m: dict) -> float:
    return m["admit_ms"] + m["dispatch_ms"] + m["deliver_ms"]


@es.never_raises
def read(run: dict):
    its = es.iterations(run)
    if not its:
        return None
    lo, hi = run["window_wall"]
    m = means(its)
    replicas = len({s.get("pid") for s in its})
    wall_ms = (hi - lo) * 1000.0 * replicas
    print(f"sched_host_ms: {len(its)} passes of {replicas} replica(s) in the "
          f"window; mean ms a pass: "
          + ", ".join(f"{k[:-3]} {m[k]:.3f}" for k in PHASES)
          + f"; of the window's wall time sync "
          f"{100 * sum(s['at']['sync_ms'] for s in its) / wall_ms:.1f}%, "
          f"idle {100 * sum(s['at']['idle_ms'] for s in its) / wall_ms:.1f}%, "
          f"the host's own work "
          f"{100 * host_ms(m) * len(its) / wall_ms:.1f}%", flush=True)
    window = sp.traced_window(run)
    pid = (run.get("profile") or {}).get("replica_pid")
    traced = [s for s in its if window and window[0] <= s["a"] < window[1]
              and (pid is None or s.get("pid") == pid)]
    if traced:
        print(f"sched_host_ms: in the profiler's window {len(traced)} passes, "
              f"the host's own work {host_ms(means(traced)):.3f} ms a pass",
              flush=True)
    return host_ms(m)
