"""The harness end to end on the CPU with a toy of the EvaByte block
(`configs/tiny-eva.json`: hidden 64, 2 layers, a window of 32 positions that
starts over, one summary a chunk of 4, 4 heads of 16, a head of 8 x 320
columns), traced and untraced, through a manifest of its own
(`BENCHMARK.tiny-eva.json`): the plain reference `reference/evabyte.py`
decides `correct` on prompts of 30 and 60 tokens whose answers cross a
window's edge, and the new readers find both leaves' rows on the
`engine.dispatch_chunk` spans. Nothing here is a device number. Run by hand:
`pytest benchmark/tests -q`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "benchmark/tests/BENCHMARK.tiny-eva.json")


def run(trace, seed):
    cmd = [sys.executable, "benchmark/run.py", "--workload", "tinyeva.closed",
           "--seed", str(seed), "--seconds", "4", "--trace", str(trace),
           "--manifest", MANIFEST]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RT_TRACING", None)
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_the_tiny_eva_model_is_served_checked_and_counted(trace):
    line, out = run(trace, 2**31 + 49)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    assert "the reader failed" not in out
    got = line["metrics"]
    if not trace:
        assert set(got) == {"out_tok_s", "tpot_p95_ms", "setup_s"}
        return
    # a CPU trace has no device plane: the device's readers return nothing
    assert set(got) == {"host_sync_ms", "batch_occupancy", "sched_host_ms",
                        "eva_walk_over_visible", "eva_summary_row_share"}
    assert "eva_walk_over_visible:" in out
    # each leaf walked to the quarter of 32 rows that holds its longest
    # live stop: at least what is visible, at most a few times that
    assert 1.0 <= got["eva_walk_over_visible"]["value"] < 4.0
    # prompts of 8 to 64 on a window of 32: some slots past their first
    assert 0 < got["eva_summary_row_share"]["value"] < 60
