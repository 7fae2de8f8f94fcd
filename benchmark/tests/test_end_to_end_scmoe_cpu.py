"""The harness end to end on the CPU with a toy of the shortcut-connected
expert layer (`configs/tiny-scmoe.json`: hidden 64, 2 layers of two latent
attentions of 4 heads of 16 + 8, two dense SwiGLUs of 128 and one expert
layer; 16 routed experts top-4 of which this share holds 4, and 8 identity
experts, a third of the router's 24 outputs), traced and untraced, through a
manifest of its own (`BENCHMARK.tiny-scmoe.json`, which lists EVERY reader
the real cell runs: the nine without a `workloads` list and this
configuration's three): the plain reference `reference/longcat_flash.py`
decides `correct` on prompts of 30 and 60 tokens, and the new readers find
the identity experts' counters on `engine.host_sync`. Nothing here is a
device number. Run by hand: `pytest benchmark/tests -q`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "benchmark/tests/BENCHMARK.tiny-scmoe.json")
NEW = ("scmoe_step_roofline", "scmoe_expert_rows_per_step",
       "zero_expert_share")


def run(trace, seed):
    cmd = [sys.executable, "benchmark/run.py", "--workload",
           "tinyscmoe.closed", "--seed", str(seed), "--seconds", "4",
           "--trace", str(trace), "--manifest", MANIFEST]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RT_TRACING", None)
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_the_tiny_shortcut_model_is_served_checked_and_counted(trace):
    line, out = run(trace, 2**31 + 42)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    assert "the reader failed" not in out
    got = line["metrics"]
    if not trace:
        assert set(got) == {"tpot_p95_ms", "setup_s"}
        return
    # a CPU trace has no device plane: the device's readers (the roofline
    # share among them) return nothing; the spans' readers all ran
    assert set(got) >= {"host_sync_ms", "sched_host_ms",
                        "scmoe_expert_rows_per_step", "zero_expert_share"}
    assert not set(got) & {"scmoe_step_roofline", "decode_step_ms",
                           "prefill_dev_share", "idle_host_share"}
    assert "scmoe_expert_rows_per_step:" in out and "zero_expert_share:" in out
    # 4 slots x 4 selections x 4 of 24 outputs held: 2.7 rows a step a
    # layer if the routing were uniform; a third of the outputs identity
    assert 0 <= got["scmoe_expert_rows_per_step"]["value"] <= 16
    assert 0 < got["zero_expert_share"]["value"] < 1


def test_a_program_without_the_counters_leaves_the_metrics_out():
    """What the parent commit, or a model whose router has no identity
    experts, gives the new readers: `engine.host_sync` spans without
    `moe_picks`, or an `arch` without a `zero_expert_num`."""
    sys.path.insert(0, ROOT)
    from benchmark import manifest

    with open(os.path.join(ROOT, "benchmark/tests/configs/tiny-scmoe.json")
              ) as f:
        llm = json.load(f)["llm_config"]
    spans = [{"n": "engine.dispatch_chunk", "k": "engine", "a": 1.0, "b": 1.1,
              "pid": 1, "at": {"tokens": 8, "active": 4, "kv_bound": 40,
                               "kv_rows": 64, "kv_rows_full": 64,
                               "kv_live_full": 36.5, "seq": 7}},
             {"n": "engine.host_sync", "k": "engine", "a": 1.0, "b": 1.1,
              "at": {"seq": 7, "tokens": 8, "moe_rows": 2 * 8 * 3,
                     "moe_rows_busiest": 20, "moe_steps": 8}}]
    profile = {"devices": [{"programs": {"jit_chunk": 0.08},
                            "loop_steps": {"jit_chunk": 8}}],
               "profile_start_ns": 0.5e9, "profile_stop_ns": 1.5e9,
               "replica_pid": 1}
    run_ = {"spans": spans, "window_wall": (0.0, 2.0), "records": [],
            "profile": profile, "device": {"kind": "TPU v5e"},
            "config": {"llm_config": llm, "app_kwargs": {"max_batch": 4}}}
    # the parent's spans: rows, but none of the identity experts' counters
    assert manifest.layer_reader("scmoe_step_roofline")(run_) is None
    assert manifest.layer_reader("zero_expert_share")(run_) is None
    assert manifest.layer_reader("scmoe_expert_rows_per_step")(run_) == 3.0
    for name in NEW:  # another model, whatever its spans
        plain = dict(run_, config={"llm_config": {"n_layers": 2},
                                   "app_kwargs": {"max_batch": 4}})
        assert manifest.layer_reader(name)(plain) is None
        assert manifest.layer_reader(name)(dict(run_, spans=[])) is None
    # with the counters: the share from the spans alone, and the roofline
    # share from the (made-up) device seconds: a share of a floor
    spans[1]["at"].update(moe_picks=8 * 4 * 4 * 2, moe_zero_picks=64,
                          moe_touched=8 * 2 * 3)
    assert manifest.layer_reader("zero_expert_share")(run_) == 0.25
    assert 0 < manifest.layer_reader("scmoe_step_roofline")(run_) < 100
    # a chunk whose read names another ordinal: the window's counts serve
    spans[1]["at"]["seq"] = 8
    assert 0 < manifest.layer_reader("scmoe_step_roofline")(run_) < 100
    # without a device trace the roofline has nothing to divide by
    assert manifest.layer_reader("scmoe_step_roofline")(
        dict(run_, profile=None)) is None
