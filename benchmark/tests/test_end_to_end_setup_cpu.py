"""The replica's set-up account end to end on the CPU: a traced run of the
toy (`configs/tiny-64d.json`) through a manifest of its own
(`BENCHMARK.tiny-setup.json`) shows that the six `setup_*` readers get a
number from the file the real program writes and that their check adds up;
an untraced run writes no file and reports the end-to-end metrics alone.
Nothing here is a device number. Run by hand: `pytest benchmark/tests -q`."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "benchmark/tests/BENCHMARK.tiny-setup.json")
SIX = {"setup_trace_lower_s", "setup_compile_s", "setup_first_run_s",
       "setup_runtime_init_s", "setup_engine_init_s", "setup_outside_s"}


def run(trace, seed, tmp):
    cmd = [sys.executable, "benchmark/run.py", "--workload",
           "tinysetup.closed", "--seed", str(seed), "--seconds", "4",
           "--trace", str(trace), "--manifest", MANIFEST]
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp))
    env.pop("RT_TRACING", None)
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_the_account_file_is_written_and_read_only_when_traced(
        trace, tmp_path):
    line, out = run(trace, 2**31 + 56, tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert "the reader failed" not in out
    files = os.path.join(tmp_path, "ray_tpu_bench", "setup")
    if not trace:
        assert set(line["metrics"]) == {"out_tok_s", "tpot_p95_ms", "setup_s"}
        assert not os.path.exists(files)
        return
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert SIX <= set(got) and all(got[k] >= 0 for k in SIX)
    (name,) = os.listdir(files)
    with open(os.path.join(files, name)) as f:
        doc = json.load(f)
    assert f"replica {doc['pid']}:" in out
    # the toy's programs: every length of chunk, four prompt buckets
    names = [b["fun_name"] for b in doc["builds"]]
    assert names.count("jit_prefill") >= 3 and names.count("jit_chunk") >= 6
    assert doc["compile_count"] == len(doc["builds"])
    # the check's own line: the six and the rest against setup_s
    check = re.search(r"together ([\d.]+)s against setup_s ([\d.]+)s", out)
    assert abs(float(check.group(1)) - float(check.group(2))) < 2.0
