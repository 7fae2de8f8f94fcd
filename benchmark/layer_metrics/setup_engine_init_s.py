"""setup_engine_init_s — layer: replica set-up (the process's set-up
account, `ray_tpu/_private/telemetry.py`; `benchmark/setup_spans.py`).

Seconds of the `engine.init` stage that are no build: its duration less
the builds inside it (the parameter-making program, the layout probe, the
longest sampled chunk program compiled for `cache_boundary_copies`, the
eager programs of the constructor). What is left is making, casting and
placing the parameters and the host's own work. Printed: the stage, its
children `engine.params` and `engine.programs`, and the builds inside.
The slowest replica's."""

from benchmark import engine_spans as es, setup_spans as su


@es.never_raises
def read(run: dict):
    def one(acct):
        st = su.stage(acct, "engine.init")
        if st is None:
            return None
        inside = [b for b in acct["builds"]
                  if st["a"] <= b["a"] and b["b"] <= st["b"]]
        kids = ", ".join(
            f"{n} {secs:.2f}s" for n in ("engine.params", "engine.programs")
            if (secs := su.stage_s(acct, n)) is not None)
        print(f"setup_engine_init_s: replica {acct['pid']}: engine.init "
              f"{st['b'] - st['a']:.2f}s ({kids}), {len(inside)} builds "
              f"inside it of {sum(b['b'] - b['a'] for b in inside):.2f}s: "
              + ", ".join(f"{b['fun_name']} {b['b'] - b['a']:.2f}"
                          for b in sorted(inside,
                                          key=lambda b: b["a"] - b["b"])[:5]),
              flush=True)
        return su.engine_init_self_s(acct)
    return su.slowest(run, one)
