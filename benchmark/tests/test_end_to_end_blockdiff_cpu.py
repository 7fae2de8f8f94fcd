"""The harness end to end on the CPU with a toy of the block-diffusion,
expert-layer block (`configs/tiny-blockdiff.json`: hidden 48, 2 layers, 4
heads on 2 key/value heads, 8 experts top-2 all held, blocks of 4 positions
in 4 denoising steps), traced and untraced, through a manifest of its own
(`BENCHMARK.tiny-blockdiff.json`): the plain reference `reference/sdar.py`
replays the two greedy check answers decision by decision (prompts of 29 and
62 tokens: P mod 4 = 1 and 2, answers of 10 whose last block is cut), and
the new readers find the forwards, commits and tokens on the
`engine.host_sync` spans. Nothing here is a device number. Run by hand:
`pytest benchmark/tests -q`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "benchmark/tests/BENCHMARK.tiny-blockdiff.json")


def run(trace, seed):
    cmd = [sys.executable, "benchmark/run.py", "--workload",
           "tinyblockdiff.closed", "--seed", str(seed), "--seconds", "4",
           "--trace", str(trace), "--manifest", MANIFEST]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RT_TRACING", None)
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_the_tiny_block_model_is_served_checked_and_counted(trace):
    line, out = run(trace, 2**31 + 58)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    assert "the reader failed" not in out
    # (`correct` also says that no program was built inside the window)
    got = line["metrics"]
    if not trace:
        assert set(got) == {"out_tok_s", "tpot_p95_ms", "setup_s"}
        return
    # a CPU trace has no device plane: the device's readers return nothing
    assert set(got) == {"host_sync_ms", "batch_occupancy", "sched_host_ms",
                        "blockdiff_tokens_per_forward",
                        "blockdiff_expert_rows_per_step"}
    # between L / (T + 1) and L / 2 tokens a forward; on these weights no
    # confidence reaches 0.9, and a first or last block gives fewer
    assert 0.5 < got["blockdiff_tokens_per_forward"]["value"] <= 0.8
    # 4 slots x 4 positions x 2 selections / 8 experts, were it uniform
    assert 0 < got["blockdiff_expert_rows_per_step"]["value"] <= 16
    assert got["blockdiff_expert_rows_per_step"]["value"] == pytest.approx(
        4.0, rel=1e-6)  # every row selects 2 of 8, all of them held
