"""The traced run end to end on the CPU with the engine's spans and their
readers in place, through a manifest of its own
(`BENCHMARK.tiny-traced.json`: the tiny cells with every per-layer metric
of the repository's manifest). The four-replica traced run is the path a
PR was once lost to (PERF.md, PR 25); nothing else runs it. Nothing here is
a device number. Run by hand: `pytest benchmark/tests -q`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRACED = os.path.join(ROOT, "benchmark/tests/BENCHMARK.tiny-traced.json")

OPEN_LOOP = {"loadgen_late_ms", "admit_wait_ms", "ttft_p50_ms", "ttft_p95_ms",
             "engine_queue_ms", "ready_wait_ms", "first_token_read_ms"}
# The device's account (PR 36) needs no device plane, only chunks that the
# device, not the scheduler's loop, paces: this toy may or may not give its
# readers enough of them (`test_end_to_end_account_cpu.py` has a toy that
# does).
ACCOUNT = {"decode_step_window_ms", "admit_dev_share_window", "admit_dev_ms",
           "handover_gap_share_window"}


def traced_run(workload, seed):
    cmd = [sys.executable, "benchmark/run.py", "--workload", workload,
           "--seed", str(seed), "--seconds", "4", "--trace", "1",
           "--manifest", TRACED]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RT_TRACING", None)
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("workload, replicas, want", [
    ("tinyx4.open", 4, OPEN_LOOP | {"host_sync_ms", "sched_host_ms"}),
    ("tiny.closed", 1, {"host_sync_ms", "batch_occupancy", "sched_host_ms"}),
])
def test_a_traced_run_reports_the_engines_metrics(workload, replicas, want):
    line, out = traced_run(workload, 2**31 + 23)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    assert line["device"]["count"] == replicas
    # a CPU trace has no device plane: the device's readers return nothing,
    # and this toy has no expert layer for the experts' readers to count
    assert want <= set(line["metrics"]) <= want | ACCOUNT
    assert all(m["value"] >= 0 for m in line["metrics"].values())
    assert f"of {replicas} replica(s) in the window" in out
    assert "the reader failed" not in out
    if "first_token_read_ms" in want:
        assert "time to first token, medians in ms: admit_wait" in out
