"""The harness end to end on the CPU with a toy of the window-and-full
attention, expert-layer block (`configs/tiny-swa-moe.json`: hidden 48, 8
layers = 2 dense + 6 expert, three window layers of 16 rows then a full one,
4 heads on 2 key/value heads, 8 experts top-2 of which this share holds 4),
traced and untraced, through a manifest of its own
(`BENCHMARK.tiny-swa-moe.json`): the plain reference
`reference/trinity.py` decides `correct` on prompts of 30 and 60 tokens, both
longer than the window, and the new readers find the rows walked and visible
by kind of leaf on the `engine.dispatch_chunk` spans. Nothing here is a
device number. Run by hand: `pytest benchmark/tests -q`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "benchmark/tests/BENCHMARK.tiny-swa-moe.json")
NEW = ("swa_moe_step_roofline", "kv_walk_over_visible",
       "held_expert_rows_per_step")


def run(trace, seed):
    cmd = [sys.executable, "benchmark/run.py", "--workload", "tinyswa.closed",
           "--seed", str(seed), "--seconds", "4", "--trace", str(trace),
           "--manifest", MANIFEST]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RT_TRACING", None)
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_the_tiny_window_model_is_served_checked_and_counted(trace):
    line, out = run(trace, 2**31 + 31)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    assert "the reader failed" not in out
    got = line["metrics"]
    if not trace:
        assert set(got) == {"out_tok_s", "tpot_p95_ms", "setup_s"}
        return
    # a CPU trace has no device plane: the device's readers return nothing
    assert set(got) == {"host_sync_ms", "batch_occupancy", "sched_host_ms",
                        "kv_walk_over_visible", "held_expert_rows_per_step"}
    assert "kv_walk_over_visible:" in out
    # the full leaves are walked to a quarter of 128 rows, the rings to a
    # quarter of 16: at least what is visible, at most a few times that
    assert 1.0 <= got["kv_walk_over_visible"]["value"] < 4.0
    # 4 slots x 2 selections x 4 of 8 held: 4 rows a step a layer if the
    # routing were uniform
    assert 0 < got["held_expert_rows_per_step"]["value"] <= 8


def test_a_program_that_refuses_the_configuration_fails_at_once(tmp_path):
    """The cell's driver (`drivers/serve_http_preflight.py`) asks the program
    for the configuration's model before it starts anything: a `model_type`
    nobody builds ends the run in seconds with exit code 1, not after the
    deployment's 600 s."""
    with open(MANIFEST) as f:
        man = json.load(f)
    with open(os.path.join(ROOT, man["configs"][0]["file"])) as f:
        config = json.load(f)
    config["llm_config"]["arch"]["model_type"] = "nobody-builds-this"
    # (the harness looks for <dir>/traffic/<mix>.json beside <dir>/configs/)
    os.makedirs(tmp_path / "configs")
    os.makedirs(tmp_path / "traffic")
    with open(tmp_path / "configs" / "refused.json", "w") as f:
        json.dump(config, f)
    with open(os.path.join(ROOT, "benchmark/tests/traffic",
                           "tiny-closed-preflight.json")) as f:
        (tmp_path / "traffic" / "tiny-closed-preflight.json").write_text(
            f.read())
    man["configs"][0]["file"] = str(tmp_path / "configs" / "refused.json")
    with open(tmp_path / "manifest.json", "w") as f:
        json.dump(man, f)
    cmd = [sys.executable, "benchmark/run.py", "--workload", "tinyswa.closed",
           "--seed", "1", "--seconds", "4", "--trace", "0",
           "--manifest", str(tmp_path / "manifest.json")]
    proc = subprocess.run(cmd, cwd=ROOT, env=dict(os.environ,
                                                  JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "does not build this configuration" in proc.stderr
    assert "nobody-builds-this" in proc.stderr


def test_a_program_without_the_attributes_leaves_the_metrics_out():
    """What the parent commit, or a model without window layers, gives the
    new readers: chunk spans without the rows by kind, or an `arch` without
    `layer_types`."""
    sys.path.insert(0, ROOT)
    from benchmark import manifest

    with open(os.path.join(ROOT, "benchmark/tests/configs/tiny-swa-moe.json")
              ) as f:
        llm = json.load(f)["llm_config"]
    spans = [{"n": "engine.dispatch_chunk", "k": "engine", "a": 1.0, "b": 1.1,
              "pid": 1, "at": {"tokens": 8, "active": 4, "kv_bound": 40,
                               "kv_rows": 64}},
             {"n": "engine.host_sync", "k": "engine", "a": 1.0, "b": 1.1,
              "at": {"chunks": 1, "cols": 16}}]
    run_ = {"spans": spans, "window_wall": (0.0, 2.0), "records": [],
            "profile": None, "device": {"kind": "cpu"},
            "config": {"llm_config": llm, "app_kwargs": {"max_batch": 4}}}
    for name in NEW:
        assert manifest.layer_reader(name)(run_) is None
        plain = dict(run_, config={"llm_config": {"n_layers": 2},
                                   "app_kwargs": {"max_batch": 4}})
        assert manifest.layer_reader(name)(plain) is None
    # and with them, the walk's ratio is read from the spans alone
    spans[0]["at"].update(kv_rows_full=64, kv_rows_window=16,
                          kv_live_full=36.5, kv_live_window=16.0)
    assert manifest.layer_reader("kv_walk_over_visible")(run_) == (
        (2 * 64 + 6 * 16) / (2 * 36.5 + 6 * 16.0))
