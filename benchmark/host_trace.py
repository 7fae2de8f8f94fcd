"""The readers of the host's phases share one call of `host_phases.py`: a child held to the CPU (reading a trace imports JAX,
and the benchmark's own process never does), run at most once in a run,
on the trace the driver left under
`<tempdir>/ray_tpu_bench/profile_<pid of this process>/`. None where there
is no profile, no trace file, or the child fails or prints nothing."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
_KEY = "host_phases"  # where a run keeps the child's answer


def trace_file() -> str | None:
    out_dir = os.path.join(tempfile.gettempdir(), "ray_tpu_bench",
                           f"profile_{os.getpid()}")
    found = sorted(os.path.join(d, f) for d, _s, fs in os.walk(out_dir)
                   for f in fs if f.endswith(".xplane.pb"))
    return found[0] if found else None


def reduce_file(path: str) -> dict | None:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(HERE, "host_phases.py"), path],
            env=env, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        print(f"host_phases: {type(e).__name__}: {e}", flush=True)
        return None
    if proc.returncode != 0:
        print(f"host_phases failed: {proc.stderr[-2000:]}", flush=True)
        return None
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError) as e:
        print(f"host_phases printed no JSON: {e}", flush=True)
        return None


def host_phases(run: dict) -> dict | None:
    """`host_phases.reduce_host` of the run's trace, or None."""
    if _KEY not in run:
        path = trace_file() if run.get("profile") else None
        run[_KEY] = reduce_file(path) if path else None
    return run[_KEY]


def device(run: dict) -> dict | None:
    """The traced device's part of it (the benchmark traces one replica)."""
    got = host_phases(run)
    return got["devices"][0] if got and got.get("devices") else None
