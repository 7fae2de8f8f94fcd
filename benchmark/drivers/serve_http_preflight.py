"""`serve_http` behind a preflight: before a cluster is started, a child held
to the CPU asks the program to derive the model the configuration describes
(`ray_tpu.llm.engine.model_config(LLMConfig(**llm_config))`, shapes only,
two seconds). A program that does not build the configuration then fails the
run at once and cleanly (`RunFailed`, exit code 1), where `serve_http` alone
would start the deployment, watch the replica's constructor raise the same
error again and again, and give up after `serve.run`'s 600 s (measured at
PR 32 with the parent commit on `trinity-mini.docs-saturated`: PERF.md
section 6). Everything else is `serve_http`'s, unchanged.

This process still never imports JAX: the question is put to a child.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmark import manifest
from benchmark.drivers import serve_http
from benchmark.drivers.serve_http import RunFailed  # noqa: F401 - run.py's

ASK = ("import json, sys\n"
       "from ray_tpu.llm import LLMConfig\n"
       "from ray_tpu.llm.engine import model_config\n"
       "model_config(LLMConfig(**json.load(sys.stdin)))\n")


def can_build(config: dict, timeout: float = 120.0) -> None:
    """Raises RunFailed where the program refuses the configuration."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["PYTHONPATH"] = manifest.ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", ASK], input=json.dumps(config["llm_config"]),
            env=env, cwd=manifest.ROOT, capture_output=True, text=True,
            timeout=timeout)
    except subprocess.TimeoutExpired:
        raise RunFailed(f"the program did not derive the configuration's "
                        f"model within {timeout:g}s") from None
    if proc.returncode != 0:
        why = (proc.stderr.strip().splitlines() or ["no message"])[-1]
        raise RunFailed(f"the program does not build this configuration: "
                        f"{why[:300]}; nothing was served")


def run(cell: dict, args) -> dict:
    can_build(cell["config"])
    return serve_http.run(cell, args)
