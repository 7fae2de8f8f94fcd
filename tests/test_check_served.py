"""`tools/check_served.py`: a cell's `correct` check without the cell (the
check prompts through `ContinuousEngine` alone, the configuration's own
reference), here on the CPU with the benchmark's toy of Kimi K2's block.
On the CPU every expert layer takes the dense arm, so `--dense` serves the
same tokens and reads the same rows."""

import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("flags", [[], ["--dense"]], ids=["served", "dense"])
def test_the_toys_check_reads_its_rows_and_exits_0(flags):
    proc = subprocess.run(
        [sys.executable, "tools/check_served.py", "tinymoe.closed",
         "--manifest", "benchmark/tests/BENCHMARK.tiny-mla-moe.json", *flags],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    result = re.search(r"RESULT tinymoe.closed: worst gap ([0-9.]+) of "
                       r"tolerance ([0-9.]+) -> ok; rows .*?(\[.*\])",
                       proc.stdout)
    assert result, proc.stdout[-2000:]
    assert float(result[1]) <= float(result[2])
    # two check prompts, each with its length, worst gap and matches
    assert len(re.findall(r"\(\d+, [0-9.]+, \d+\)", result[3])) == 2
    # the CPU's arm reads every held expert: 4 held x 2 layers x 24 steps
    assert "moe_fetched_total 192" in proc.stdout
    assert ("dense arm" if flags else "as served") in proc.stdout
