"""setup_outside_s — layer: replica set-up (the process's set-up account,
`ray_tpu/_private/telemetry.py`; `benchmark/setup_spans.py`).

Seconds of the traced run's `setup_s` that the replica's account does not
cover: `setup_s` less the wall time from `replica.start`'s begin to the
last build's end (or its call's read result) before the window. Before the
replica: imports, the cluster, the controller, the worker's spawn. After
its last build: the warm-up requests that build nothing more and the
preload. A drift that lands here is the host plane's, not the compiler's.
Printed beside it: the driver's own stopwatch (`cluster_s`, `deploy_s`,
`warmup_s`, the preload), the two sides, and the CHECK: the six `setup_*`
metrics, the covered time that none of them counts (the warm-up requests'
own prefill and decode, HTTP, the driver's polls; and what lies between a
build's parts) and `setup_s` side by side, so a hole or a second counted
twice shows. The slowest replica's
(the one whose account covers most)."""

from benchmark import engine_spans as es, setup_spans as su


@es.never_raises
def read(run: dict):
    setup_s = run["e2e"]["setup_s"]
    split = run.get("split") or {}

    def one(acct):
        span = su.covered(acct)
        if span is None:
            return None
        outside = setup_s - (span[1] - span[0])
        six = [su.trace_lower_s(acct), su.compile_s(acct),
               su.seconds(su.first_run_spans(acct)),
               su.stage_s(acct, "runtime.init") or 0.0,
               su.engine_init_self_s(acct) or 0.0, outside]
        rest, gaps = su.rest_s(acct), su.build_gaps_s(acct)
        print(f"setup_outside_s: replica {acct['pid']}: "
              f"{span[0] - acct['bench_start']:.2f}s before replica.start, "
              f"{acct['lo'] - span[1]:.2f}s after the last build; the "
              f"driver's stopwatch: " + ", ".join(
                  f"{k} {split[k]:.2f}" for k in (
                      "cluster_s", "deploy_s", "warmup_s") if k in split)
              + f", preload {run['traffic'].get('preload_s', 8):g}",
              flush=True)
        print(f"setup_outside_s: CHECK the six "
              + " + ".join(f"{v:.2f}" for v in six)
              + f" = {sum(six):.2f}s, the covered time in none of "
              f"them {rest:.2f}s outside a build and "
              f"{round(gaps, 2) + 0.0:.2f}s between the builds' parts, "
              f"together {sum(six) + rest + gaps:.2f}s against setup_s "
              f"{setup_s:.2f}s", flush=True)
        return outside
    return su.slowest(run, one, pick=min)
