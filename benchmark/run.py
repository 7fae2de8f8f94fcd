"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that starts the cluster, deploys the cell's configuration,
warms up, offers the cell's traffic, measures for --seconds, shuts down,
checks what was served against the plain reference and prints its result as
the last line of its output: one JSON object with `correct`, `attempted`,
`failed`, `metrics` and `device` (and `breakdown` with --trace 1). With
--trace 0 the metrics are the cell's end-to-end metrics; with --trace 1 the
program's spans are on and a device trace is taken inside the window, and the
metrics are the cell's per-layer metrics.

Everything a cell is made of is data, found by name: BENCHMARK.json names
the configuration file and the traffic mix; the traffic file names its
driver; each per-layer metric has a reader of its own under layer_metrics/.
It exits non-zero, and prints no result, when the host lacks the chips the
cell asks for, and takes no notice of BENCH_RUN.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def breakdown(profile: dict | None) -> dict | None:
    """The device operations that took most time and the longest idle gaps
    of the traced replica. A gap is named by its place in the traced window
    only: saying what the host was doing in it needs host spans on the
    profiler's clock, which the program does not write yet."""
    if not profile or not profile["devices"]:
        return None
    dev = profile["devices"][0]
    return {"device_ops": [[name, secs] for name, secs in dev["ops"]],
            "idle_gaps": [[f"gap at +{(start - dev['first_ns']) / 1e9:.3f}s "
                           f"of the traced window", secs]
                          for start, secs in dev["gaps"]]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another manifest than the repository's (the "
                         "benchmark's own tests use a tiny one)")
    args = ap.parse_args()

    from benchmark import manifest

    cell = manifest.load_cell(args.manifest, args.workload)
    driver_path = cell["traffic"]["driver"]
    driver = importlib.import_module(
        driver_path.removesuffix(".py").replace("/", "."))
    try:
        run = driver.run(cell, args)
    except driver.RunFailed as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 1
    for p in run["problems"]:
        print(f"benchmark: NOT CORRECT: {p}", flush=True)

    units = {m["name"]: m["unit"]
             for m in cell["end_to_end"] + cell["per_layer"]}
    metrics = {}
    if args.trace:
        run.update(config=cell["config"], traffic=cell["traffic"])
        for m in cell["per_layer"]:
            reader = manifest.layer_reader(m["name"])
            value = reader(run) if reader else None
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
        print("end to end in the traced run (tracing is on: not the "
              "numbers to judge by): " + json.dumps(run["e2e"]), flush=True)
    else:
        for m in cell["end_to_end"]:
            if m["name"] in run["e2e"]:
                metrics[m["name"]] = {"value": run["e2e"][m["name"]],
                                      "unit": units[m["name"]]}
    line = {"correct": run["correct"], "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics,
            "device": dict(run["device"])}
    if args.trace:
        prof = run.get("profile")
        if prof and prof["devices"]:
            busy = [d["busy_s"] for d in prof["devices"]]
            line["device"]["busy_s"] = sum(busy) / len(busy)
            line["device"]["window_s"] = prof["window_s"]
        bd = breakdown(prof)
        if bd:
            line["breakdown"] = bd
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
