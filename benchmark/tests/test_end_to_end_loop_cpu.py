"""The harness end to end on the CPU with a toy of the Ouro block
(`configs/tiny-loop.json`: hidden 64, 2 layers run THREE times a token with
one set of weights and three pairs of cache leaves a layer, 4 heads of 16),
traced and untraced, through a manifest of its own
(`BENCHMARK.tiny-loop.json`): the plain reference `reference/ouro.py`
decides `correct` on prompts of 30 and 60 tokens, and the new reader finds
the passes on the `engine.dispatch_chunk` spans. Nothing here is a device
number. Run by hand: `pytest benchmark/tests -q`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "benchmark/tests/BENCHMARK.tiny-loop.json")


def run(trace, seed):
    cmd = [sys.executable, "benchmark/run.py", "--workload",
           "tinyloop.closed", "--seed", str(seed), "--seconds", "4",
           "--trace", str(trace), "--manifest", MANIFEST]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RT_TRACING", None)
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_the_tiny_looped_model_is_served_checked_and_counted(trace):
    line, out = run(trace, 2**31 + 53)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    assert "the reader failed" not in out
    got = line["metrics"]
    if not trace:
        assert set(got) == {"out_tok_s", "tpot_p95_ms", "setup_s"}
        return
    # a CPU trace has no device plane: the device's readers return nothing
    assert set(got) == {"host_sync_ms", "batch_occupancy", "sched_host_ms",
                        "ouro_cache_share"}
    assert "ouro_cache_share:" in out and "3 passes" in out
    # prompts of 8 to 64 and short answers on weights of 0.4 MB: the rows
    # are a share, neither nothing nor all
    assert 5 < got["ouro_cache_share"]["value"] < 60
