"""setup_trace_lower_s — layer: replica set-up (the process's set-up
account, `ray_tpu/_private/telemetry.py`; `benchmark/setup_spans.py`).

Seconds the replica spent TRACING its programs and LOWERING them to MLIR
before the window: summed `trace_s + lower_s` of its builds. The compile
cache takes none of it away, and passes written out and more programs
multiply it. Printed: the builds and the five costliest program names. The
slowest replica's."""

from benchmark import engine_spans as es, setup_spans as su


@es.never_raises
def read(run: dict):
    def one(acct):
        print(f"setup_trace_lower_s: replica {acct['pid']}: "
              f"{len(acct['builds'])} builds, tracing "
              f"{sum(b['trace_s'] for b in acct['builds']):.2f}s, lowering "
              f"{sum(b['lower_s'] for b in acct['builds']):.2f}s; costliest: "
              + su.top(acct, lambda b: b["trace_s"] + b["lower_s"]),
              flush=True)
        return su.trace_lower_s(acct)
    return su.slowest(run, one)
