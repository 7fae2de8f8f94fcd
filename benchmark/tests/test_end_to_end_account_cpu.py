"""The device's account end to end on the CPU: a traced run of a toy large
enough that the CPU, not the scheduler's loop, paces the chunks
(`configs/tiny-account.json`: hidden 192, 4 layers), through a manifest of
its own (`BENCHMARK.tiny-account.json`), shows that the four readers of
`benchmark/device_account.py` get a number from what the real program
writes, closed loop and open; and the same readers on spans without the
attributes, which is what the parent commit writes, return None. Nothing
here is a device number. Run by hand: `pytest benchmark/tests -q`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "benchmark/tests/BENCHMARK.tiny-account.json")
NEW = {"decode_step_window_ms", "admit_dev_share_window", "admit_dev_ms",
       "handover_gap_share_window"}


def traced_run(workload, seed):
    cmd = [sys.executable, "benchmark/run.py", "--workload", workload,
           "--seed", str(seed), "--seconds", "4", "--trace", "1",
           "--manifest", MANIFEST]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RT_TRACING", None)
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("workload", ["tinyacct.closed", "tinyacct.open"])
def test_the_real_programs_spans_give_the_four_readers_a_number(workload):
    line, out = traced_run(workload, 2**31 + 41)
    assert line["correct"] is True and line["failed"] == 0
    assert "the reader failed" not in out
    got = line["metrics"]
    # a CPU trace has no device plane: the trace's own readers return
    # nothing, the account's need none
    assert NEW <= set(got) and not {"decode_step_ms", "prefill_dev_share",
                                    "idle_host_share"} & set(got)
    assert 0 < got["decode_step_window_ms"]["value"] < 1000
    assert 0 <= got["admit_dev_share_window"]["value"] < 100
    assert 0 < got["admit_dev_ms"]["value"] < 1000
    assert 0 <= got["handover_gap_share_window"]["value"] < 100
    assert "clean intervals used of" in out and "coverage" in out
    assert "prefills and" in out and "admission programs" in out
    assert "chunks were enqueued into an empty pipeline" in out


def test_spans_without_the_attributes_leave_the_four_metrics_out():
    """What the parent commit gives the new readers."""
    sys.path.insert(0, ROOT)
    from benchmark import manifest

    spans = []
    for i in range(8):
        spans += [
            {"n": "engine.dispatch_chunk", "k": "engine", "pid": 1, "t": "x",
             "a": 1.0 + 0.1 * i, "b": 1.001 + 0.1 * i,
             "at": {"tokens": 8, "active": 4, "sampler": "select",
                    "kv_bound": 40, "kv_rows": 64, "kv_rows_full": 64,
                    "kv_live_full": 30.0}},
            {"n": "engine.host_sync", "k": "engine", "pid": 1, "t": "x",
             "a": 1.05 + 0.1 * i, "b": 1.1 + 0.1 * i,
             "at": {"chunks": 1, "cols": 8}},
            {"n": "engine.prefill", "k": "engine", "pid": 1, "t": f"r{i}",
             "a": 1.02 + 0.1 * i, "b": 1.021 + 0.1 * i,
             "at": {"prompt_len": 20, "bucket": 32, "what": "dispatch",
                    "attention": "xla"}}]
    run_ = {"spans": spans, "window_wall": (0.0, 2.0), "records": [],
            "profile": None, "device": {"kind": "cpu"},
            "config": {"llm_config": {"n_layers": 2},
                       "app_kwargs": {"max_batch": 4}}}
    for name in sorted(NEW):
        assert manifest.layer_reader(name)(run_) is None
    # the older readers of the same spans still read them
    assert manifest.layer_reader("host_sync_ms")(run_) == pytest.approx(50.0)
    assert manifest.layer_reader("batch_occupancy")(run_) == 1.0
