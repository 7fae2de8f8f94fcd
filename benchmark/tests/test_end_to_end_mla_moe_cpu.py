"""The harness end to end on the CPU with a toy of the latent-attention,
expert-layer block (`configs/tiny-mla-moe.json`: hidden 64, 3 layers, 16
experts top-4 of which this share holds 4), traced and untraced, through a
manifest of its own (`BENCHMARK.tiny-mla-moe.json`): the plain reference
`reference/kimi_k2.py` decides `correct`, and the readers of the expert
layers' counters find them on the `engine.host_sync` spans. Nothing here is
a device number. Run by hand: `pytest benchmark/tests -q`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "benchmark/tests/BENCHMARK.tiny-mla-moe.json")


def run(trace, seed):
    cmd = [sys.executable, "benchmark/run.py", "--workload", "tinymoe.closed",
           "--seed", str(seed), "--seconds", "4", "--trace", str(trace),
           "--manifest", MANIFEST]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RT_TRACING", None)
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_the_tiny_expert_model_is_served_checked_and_counted(trace):
    line, out = run(trace, 2**31 + 29)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    assert "the reader failed" not in out
    assert "min_route_margin" in out  # the reference reports its margins
    got = line["metrics"]
    if not trace:
        assert set(got) == {"out_tok_s", "tpot_p95_ms", "setup_s"}
        return
    # a CPU trace has no device plane: the device's readers return nothing
    assert set(got) == {"host_sync_ms", "batch_occupancy", "sched_host_ms",
                        "expert_rows_per_step", "expert_load_imbalance"}
    # 4 slots x 4 selections x 4 of 16 held: 4 rows a step a layer if the
    # routing were uniform; the busiest of 4 experts has at least the mean
    assert 0 < got["expert_rows_per_step"]["value"] <= 16
    assert 1.0 <= got["expert_load_imbalance"]["value"] <= 4.0


def test_a_program_without_the_counters_leaves_the_metrics_out():
    """What the parent commit gives the new readers: spans without the
    attributes, or a configuration without `arch`."""
    sys.path.insert(0, ROOT)
    from benchmark import manifest

    spans = [{"n": "engine.host_sync", "k": "engine", "a": 1.0, "b": 1.1,
              "at": {"chunks": 1, "cols": 16}}]
    run_ = {"spans": spans, "window_wall": (0.0, 2.0), "records": [],
            "profile": None, "device": {"kind": "cpu"},
            "config": {"llm_config": {"arch": {"first_k_dense_replace": 1,
                                               "n_routed_experts": 16},
                                      "n_layers": 3, "experts_held": 4},
                       "app_kwargs": {"max_batch": 4}}}
    for name in ("expert_rows_per_step", "expert_load_imbalance",
                 "mla_moe_step_roofline"):
        assert manifest.layer_reader(name)(run_) is None
        plain = dict(run_, config={"llm_config": {"n_layers": 2},
                                   "app_kwargs": {"max_batch": 4}})
        assert manifest.layer_reader(name)(plain) is None
