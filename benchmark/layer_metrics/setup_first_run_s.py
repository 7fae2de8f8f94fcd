"""setup_first_run_s — layer: replica set-up (the process's set-up account,
`ray_tpu/_private/telemetry.py`; `benchmark/setup_spans.py`).

Seconds a program's FIRST call cost after it had its executable: loading
it, its first execution, the read of its result. For each call of the
engine's that built a program and whose result was read, `ready_s -
trace_s - lower_s - compile_s` of its builds, summed; a second counted once
where two such calls lie inside one another (`setup_spans.first_run_spans`).
Printed apart: the calls of a serving program whose attention is a Mosaic
kernel (`kernel`, by the engine's own `_prefill_form` / `_decode_form`) and
the others, each call's own wait less every build (the two may overlap).
The hand-over program's result is never read: what its first run costs
shows in the chunk dispatched behind it. The slowest replica's."""

from benchmark import engine_spans as es, setup_spans as su


@es.never_raises
def read(run: dict):
    def one(acct):
        split = {True: [], False: []}
        building = su.build_spans(acct)
        for c in su.calls(acct).values():
            main = next((b for b in c["builds"]
                         if b["fun_name"] in su.SERVING), c["builds"][0])
            own = su.seconds(su.less([c["span"]], building))
            split[bool(main.get("kernel"))].append((main["fun_name"], own))
        for kernel, rows in split.items():
            print(f"setup_first_run_s: replica {acct['pid']}: {len(rows)} "
                  f"first calls {'with' if kernel else 'without'} a Mosaic "
                  f"kernel, {sum(v for _n, v in rows):.2f}s of their waits "
                  f"outside a build: " + ", ".join(
                      f"{n} {v:.2f}" for n, v in sorted(
                          rows, key=lambda r: -r[1])[:8]), flush=True)
        return su.seconds(su.first_run_spans(acct))
    return su.slowest(run, one)
