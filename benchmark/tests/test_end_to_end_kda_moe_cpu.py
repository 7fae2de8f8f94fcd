"""The harness end to end on the CPU with a toy of the gated-delta-rule,
latent-attention, expert-layer block (`configs/tiny-kda-moe.json`: hidden 64,
8 layers = 1 dense + 7 expert, three KDA layers of 4 heads of 16 then a
latent layer, twice; 8 experts top-2 of which this share holds 4), traced and
untraced, through a manifest of its own (`BENCHMARK.tiny-kda-moe.json`): the
plain reference `reference/kimi_linear.py` decides `correct` on prompts of 30
and 60 tokens (a state handed on from inside a padded bucket each time), and
the new readers find the state's traffic on the `engine.dispatch_chunk` spans
and the expert rows on `engine.host_sync`. Nothing here is a device number.
Run by hand: `pytest benchmark/tests -q`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "benchmark/tests/BENCHMARK.tiny-kda-moe.json")
NEW = ("kda_moe_step_roofline", "kda_expert_rows_per_step")


def run(trace, seed):
    cmd = [sys.executable, "benchmark/run.py", "--workload", "tinykda.closed",
           "--seed", str(seed), "--seconds", "4", "--trace", str(trace),
           "--manifest", MANIFEST]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RT_TRACING", None)
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_the_tiny_state_model_is_served_checked_and_counted(trace):
    line, out = run(trace, 2**31 + 34)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    assert "the reader failed" not in out
    got = line["metrics"]
    if not trace:
        assert set(got) == {"out_tok_s", "tpot_p95_ms", "setup_s"}
        return
    # a CPU trace has no device plane: the device's readers (the roofline
    # share among them) return nothing; the five without a `workloads` list
    # and the two new ones all ran
    assert set(got) == {"host_sync_ms", "batch_occupancy", "sched_host_ms",
                        "kda_expert_rows_per_step"}
    assert "kda_expert_rows_per_step:" in out
    # 4 slots x 2 selections x 4 of 8 held: 4 rows a step a layer if the
    # routing were uniform
    assert 0 < got["kda_expert_rows_per_step"]["value"] <= 8


def test_a_program_without_the_attributes_leaves_the_metrics_out():
    """What the parent commit, or a model without state layers, gives the
    new readers: chunk spans without `state_rw_bytes`, or an `arch` without
    a `linear_attn_config`."""
    sys.path.insert(0, ROOT)
    from benchmark import manifest

    with open(os.path.join(ROOT, "benchmark/tests/configs/tiny-kda-moe.json")
              ) as f:
        llm = json.load(f)["llm_config"]
    spans = [{"n": "engine.dispatch_chunk", "k": "engine", "a": 1.0, "b": 1.1,
              "pid": 1, "at": {"tokens": 8, "active": 4, "kv_bound": 40,
                               "kv_rows": 64, "kv_rows_full": 64,
                               "kv_live_full": 36.5}},
             {"n": "engine.host_sync", "k": "engine", "a": 1.0, "b": 1.1,
              "at": {"chunks": 1, "cols": 16}}]
    profile = {"devices": [{"programs": {"jit_chunk": 0.08},
                            "loop_steps": {"jit_chunk": 8}}],
               "profile_start_ns": 0.5e9, "profile_stop_ns": 1.5e9,
               "replica_pid": 1}
    run_ = {"spans": spans, "window_wall": (0.0, 2.0), "records": [],
            "profile": profile, "device": {"kind": "TPU v5e"},
            "config": {"llm_config": llm, "app_kwargs": {"max_batch": 4}}}
    for name in NEW:
        assert manifest.layer_reader(name)(run_) is None
        plain = dict(run_, config={"llm_config": {"n_layers": 2},
                                   "app_kwargs": {"max_batch": 4}})
        assert manifest.layer_reader(name)(plain) is None
    # with the counters, the rows are read from the spans alone
    spans[1]["at"].update(moe_rows=7 * 8 * 4, moe_rows_busiest=80,
                          moe_steps=8)
    assert manifest.layer_reader("kda_expert_rows_per_step")(run_) == 4.0
    # and with the state's traffic on the chunk, the roofline share from
    # the (made-up) device seconds: a share of a floor, not over 100%
    spans[0]["at"]["state_rw_bytes"] = 1
    assert 0 < manifest.layer_reader("kda_moe_step_roofline")(run_) < 100
