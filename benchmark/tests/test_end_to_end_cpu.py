"""The harness end to end on the CPU at the tiny configuration: one replica
and four, closed and open loops, plain and traced. The tiny configuration,
its traffic mixes and its cells are added just as a later PR adds one: files
of their own (tests/configs, tests/traffic) and entries in a manifest
(tests/BENCHMARK.tiny.json). Nothing here is a device number. Each run is a
process of its own, as the driver makes them; they take about half a minute
each. Run by hand: `pytest benchmark/tests -q`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "benchmark/tests/BENCHMARK.tiny.json")


OPEN_LOOP_METRICS = {"latency_per_token_p50_ms", "tpot_p95_ms", "setup_s"}


def bench(workload, trace, seed=2**31 + 11, manifest=TINY, env=None):
    cmd = [sys.executable, "benchmark/run.py", "--workload", workload,
           "--seed", str(seed), "--seconds", "4", "--trace", str(trace),
           "--manifest", manifest]
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload, want", [
    ("tiny.closed", {"out_tok_s", "tpot_p95_ms", "setup_s"}),
    ("tiny.open", OPEN_LOOP_METRICS),
    ("tinyx4.open", OPEN_LOOP_METRICS),
])
def test_a_cell_reports_its_end_to_end_metrics(workload, want):
    line = last_line(bench(workload, 0))
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 or name == "tpot_p95_ms"
               for name, m in line["metrics"].items())
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == (4 if "x4" in workload else 1)


def test_a_traced_run_reports_the_per_layer_metrics_it_can_read():
    line = last_line(bench("tiny.open", 1))
    assert line["correct"] is True
    # spans are read; a CPU trace has no device plane, so the device
    # readers find nothing and their metrics are left out of the line
    assert {"loadgen_late_ms", "admit_wait_ms", "ttft_p50_ms",
            "ttft_p95_ms", "host_sync_ms"} <= set(line["metrics"])
    assert "decode_step_ms" not in line["metrics"]
    assert "busy_s" not in line["device"]


def test_the_real_cells_refuse_a_host_without_a_tpu():
    proc = bench("phi3.chat-saturated", 0,
                 manifest=os.path.join(ROOT, "BENCHMARK.json"))
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
