"""A cell's `correct` check without the cell: the traffic file's greedy check
prompts served by `ContinuousEngine` alone, in this process (the replica's
own path: prefill, place, chunk programs of the configuration's batch), and
read by the configuration's own plain reference. Two minutes where the cell
takes five to thirteen, and the same rows: the check's prompts and the
weights come from the configuration, not from a run's seed, so a program
text reads the same rows in every run (PERF.md section 6, PR 47). Whatever
the reference's `check` does with a case is done here too: for
`sdar.solve-saturated` (`reference/sdar.py`) that is the replay of a served
answer decision by decision, and `/v1/stats`' `bd_*` counts are printed.

    chiprun -- python3 tools/check_served.py kimi-k2.code-saturated
    chiprun -- python3 tools/check_served.py kimi-k2.code-saturated --dense

`--dense` serves the same prompts with the expert rule told it is not on
the chip, so every expert layer takes `models/moe.py`'s dense arm: the rows
an expert kernel has to reproduce. Exit code 0 when every row is within the
reference's tolerance. Runs wherever JAX does; only a run on the chip says
anything about the chip's programs.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def serve(config: dict, params, bodies: list) -> tuple[list, dict]:
    """`(cases, stats)`: each body's `(prompt, served tokens)`, one request
    at a time in an otherwise idle batch, as the benchmark's warm-up sends
    them; and the engine's `/v1/stats` afterwards."""
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.engine import ContinuousEngine, SamplingParams

    app = config["app_kwargs"]
    eng = ContinuousEngine(
        LLMConfig(**{**config["llm_config"], "params": params}),
        max_batch=app["max_batch"], decode_chunk=app["decode_chunk"])
    try:
        cases = []
        for body in bodies:
            tokens = eng.submit(body["prompt"], SamplingParams(
                temperature=0.0, max_tokens=body["max_tokens"])).tokens()
            deadline = time.monotonic() + 60
            while eng.num_active and time.monotonic() < deadline:
                time.sleep(0.05)
            cases.append((body["prompt"], tokens))
        return cases, eng.cache_stats()
    finally:
        eng.shutdown()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell", help="a workload of BENCHMARK.json")
    ap.add_argument("--dense", action="store_true",
                    help="every expert layer through the dense arm")
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()

    import jax

    from benchmark import manifest, traffic
    from ray_tpu._private import telemetry
    from ray_tpu.ops import attention
    from ray_tpu.ops import expert_decode

    cell = manifest.load_cell(args.manifest, args.cell)
    config, llm = cell["config"], cell["config"]["llm_config"]
    bodies = [w["body"] for w in traffic.warmup_bodies(
        cell["traffic"], llm["vocab_size"]) if w["check"]]
    if args.dense:
        expert_decode.rule = types.SimpleNamespace(
            on_tpu=lambda: False, mesh_refusal=attention.mesh_refusal,
            NOT_ASKED=attention.NOT_ASKED)
    dev = jax.devices()[0]
    print(f"{args.cell} on {dev.platform} {dev.device_kind!r}: "
          f"{len(bodies)} check prompts of "
          f"{[len(b['prompt']) for b in bodies]} tokens, "
          f"{'dense arm' if args.dense else 'as served'}", flush=True)
    # ONE tree for the engine and the reference (the configuration's seed
    # makes it; two would not fit beside the reference's float32 layers)
    reference = manifest.load_module(config["reference"])
    params = reference.served_params(llm)
    reference.served_params = lambda llm: params
    telemetry.ensure_compile_listener()  # the engine's builds, not the tree's
    t0 = time.monotonic()
    cases, stats = serve(config, params, bodies)
    print(f"served in {time.monotonic() - t0:.0f} s; "
          + ", ".join(f"{k} {stats[k]}" for k in sorted(stats)
                      if k.startswith(("moe_", "decode_steps",
                                       "prefill_rows", "bd_"))),
          flush=True)
    # What the programs cost this start (README "Tracing & timeline", the
    # set-up account): a warm start's `compile_s` is the cache's read.
    print(f"set-up account: {telemetry.ACCOUNT.one_line()}", flush=True)
    jax.clear_caches()
    t0 = time.monotonic()
    res = reference.check(llm, cases)
    worst = max(r["max_gap"] for r in res["rows"])
    ok = worst <= res["tolerance"]
    print(f"RESULT {args.cell}: worst gap {worst:.4f} of tolerance "
          f"{res['tolerance']} -> {'ok' if ok else 'NOT CORRECT'}; rows "
          f"(prompt, worst gap, tokens that are the reference's best) "
          f"{[(r['plen'], round(r['max_gap'], 4), r['argmax_matches']) for r in res['rows']]} "
          f"(reference {time.monotonic() - t0:.0f} s)", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
