"""The sweep that fixes an open-loop cell's rate. Run once, on the chip, by
the PR that adds the cell; the rate it finds goes into the traffic file and
its table into PERF.md. The benchmark itself never searches for a rate.

    python3 benchmark/sweep.py --workload <open-loop cell> --rates 3.5,4,4.5 [--seconds 30]

One process, one deployment: for each rate, 5 s of the mix at that rate, then
a window of --seconds, then a drain. The knee is the highest rate at which
at least 99% of the requests due in the window complete, none is shed, and
the number in flight at the window's end is no more than 8 above that at its
start. A cell below capacity runs at 0.8 of the knee, rounded down to 0.1.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PRELOAD_S = 5.0


def in_flight(records, t: float) -> int:
    return sum(1 for r in records if r.sent is not None and r.sent <= t
               and (r.ended is None or r.ended > t))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()

    from benchmark import manifest, stats, traffic as traffic_mod
    from benchmark.drivers import serve_http as drv

    cell = manifest.load_cell(args.manifest, args.workload)
    tr = dict(cell["traffic"], preload_s=PRELOAD_S)
    vocab = int(cell["config"]["llm_config"]["vocab_size"])

    import ray_tpu
    from ray_tpu import serve

    rows = []
    try:
        served = drv.start(cell, trace=False)
        for rate in [float(r) for r in args.rates.split(",")]:
            mix = dict(tr, rate_req_s=rate)
            reqs = traffic_mod.requests(mix, vocab, args.seed)
            t_sched0 = time.monotonic() + 0.2
            t0 = t_sched0 + PRELOAD_S
            t1 = t0 + args.seconds
            shed0 = drv.shed_total(
                drv.replica_stats(served.base, served.n_rep))
            records, window = asyncio.run(
                drv.offer(served.url, reqs, mix, t_sched0, t0, t1))
            shed = drv.shed_total(
                drv.replica_stats(served.base, served.n_rep)) - shed0
            done = [r for r in window if r.ok]
            e2e, _detail = drv.end_to_end(window, records, mix, t0, t1)
            row = {"rate_req_s": rate, "due": len(window),
                   "completed": len(done), "shed": shed,
                   "rejected": sum(1 for r in window if r.status not in
                                   (0, 200)),
                   "in_flight_start": in_flight(records, t0),
                   "in_flight_end": in_flight(records, t1),
                   "ttft_p50_ms": e2e.get("ttft_p50_ms"),
                   "ttft_p95_ms": e2e.get("ttft_p95_ms"),
                   "tpot_p95_ms": e2e.get("tpot_p95_ms"),
                   "out_tok_s": e2e.get("out_tok_s"),
                   "late_p95_ms": stats.percentile(
                       [(r.sent - r.due) * 1e3 for r in window], 95)}
            row["sustained"] = (row["completed"] >= 0.99 * row["due"]
                                and shed == 0 and row["rejected"] == 0
                                and row["in_flight_end"]
                                <= row["in_flight_start"] + 8)
            rows.append(row)
            print("sweep: " + json.dumps(row), flush=True)
            time.sleep(3.0)  # cut requests free their slots
    finally:
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001 - already down, or never up
            pass
        ray_tpu.shutdown()
    good = [r["rate_req_s"] for r in rows if r["sustained"]]
    knee = max(good) if good else None
    print(json.dumps({"knee_req_s": knee, "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
