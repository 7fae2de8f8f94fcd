"""admit_dev_ms — layer: model step (`jit_prefill`, `jit_sample1`): the
device's prefill time, over the WHOLE window and from the engine's own spans
(`benchmark/device_account.py`).

Device milliseconds an admission costs for every 1024 rows of its prefill
bucket: the admission programs' seconds of the window's intervals
(`admit_dev_share_window`'s sum: each prefill at what its bucket costs where
it was seen alone, no more than its interval's excess) over the bucket rows
of the prefills in them, x 1024. Per row and not per request, so that the
figure belongs to the prefill program and not to the mix of prompt lengths a
window happened to serve: the buckets that carry the rows cost nearly the
same a row, where a request's cost steps sixfold between buckets.

Printed by bucket: the admissions of the window, and what ONE prefill of the
bucket costs the device, the median over the intervals that hold that bucket
and nothing else (`device_account.Admissions`: between two chunks enqueued
back to back, or `firsts_ready` - `block_ready` of one `engine.host_sync`
where `engine.first_token` `sync_seq` and `engine.prefill` `after_seq` place
the prefill right after that chunk: the admission's own end, observed
directly; a bucket never seen so is priced from the hand-overs that hold it
and nothing else). That table is PERF.md's "device's prefill time per
request"."""

from benchmark import device_account as da, engine_spans as es


@es.never_raises
def read(run: dict):
    lo, hi = run["window_wall"]
    ivs = da.intervals(run, lo, hi)
    adm = da.Admissions(ivs, da.Steps(ivs))
    seen, cost = adm.seen, adm.cost
    seconds = rows = 0.0
    count: dict = {}
    for iv in ivs:
        got, _known = adm.programs(iv)
        if not iv.prefills or got is None:
            continue
        seconds += got
        rows += iv.prefill_rows
        for bucket, k in iv.buckets.items():
            count[bucket] = count.get(bucket, 0) + k
    if not rows:
        return None
    for bucket in sorted(count):
        print(f"admit_dev_ms: bucket {bucket}: {count[bucket]} admissions"
              + (f"; one prefill alone {1e3 * cost[bucket]:.2f} ms (median "
                 f"of {len(seen[bucket])} intervals, "
                 f"{1e3 * min(seen[bucket]):.2f} to "
                 f"{1e3 * max(seen[bucket]):.2f})" if bucket in seen
                 else f"; never seen alone, {1e3 * cost[bucket]:.2f} ms from "
                 f"its hand-overs less {adm.idle_of_gap:.2f} of their gaps"
                 if bucket in cost else "; never seen but beside others"),
              flush=True)
    n = sum(count.values())
    cover = da.coverage(run, lo, hi)
    print(f"admit_dev_ms: {n} admissions of {rows:.0f} bucket rows in "
          f"paired intervals, {1e3 * seconds / n:.2f} ms an admission at "
          f"this mix; coverage {100 * cover:.1f}%", flush=True)
    if cover < da.MIN_COVERAGE:
        print("admit_dev_ms: no value: coverage under "
              f"{100 * da.MIN_COVERAGE:.0f}%", flush=True)
        return None
    return 1e3 * seconds / rows * 1024
