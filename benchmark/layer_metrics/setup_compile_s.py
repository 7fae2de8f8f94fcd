"""setup_compile_s — layer: replica set-up (the process's set-up account,
`ray_tpu/_private/telemetry.py`; `benchmark/setup_spans.py`).

Seconds inside JAX's backend-compile step before the window: summed
`compile_s` of the replica's builds. In a warm run (`cache: hit`) that is
the cache entry's read and the executable's deserialisation, in a
checkout's first run the compiler and the entry's write. Printed: hits,
misses, builds the cache had no part in, the summed `retrieval_s`, and the
five costliest program names. The slowest replica's."""

from benchmark import engine_spans as es, setup_spans as su


@es.never_raises
def read(run: dict):
    def one(acct):
        by = {k: sum(b["cache"] == k for b in acct["builds"])
              for k in ("hit", "miss", "off")}
        serving = sorted({b["fun_name"] for b in acct["builds"]
                          if b["fun_name"] in su.SERVING
                          and b["cache"] != "hit"})
        print(f"setup_compile_s: replica {acct['pid']}: "
              f"{len(acct['builds'])} builds, {by['hit']} cache hits, "
              f"{by['miss']} misses, {by['off']} without the cache; "
              f"retrieval "
              f"{sum(b['retrieval_s'] for b in acct['builds']):.2f}s; "
              f"serving programs not all hits: {serving or 'none'}; "
              f"costliest: " + su.top(acct, lambda b: b["compile_s"]),
              flush=True)
        return su.compile_s(acct)
    return su.slowest(run, one)
