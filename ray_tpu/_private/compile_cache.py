"""The one place for a compiled program's identity and for where JAX keeps it.

Every process that compiles shares one directory, so that a replica, a
pipeline stage or a second run of a program finds what an earlier process
compiled. The rule, in the order it is applied:

1. `JAX_COMPILATION_CACHE_DIR` is set outside the program: that directory is
   used as it is and nothing is set here — not another directory, and not
   what is kept in it (whoever places the cache also owns JAX's
   `JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`, one second by default).
2. It is not set and the process is held to the CPU (`JAX_PLATFORMS=cpu`):
   no cache. Nothing the CPU compiles here is slow enough to need keeping,
   and on every hit XLA's CPU loader writes the machine-feature list of the
   compiling host, two lines of ~2 KB, to stderr as an error.
3. Otherwise the cache is `<checkout>/.jax_cache`, and every program is
   kept. A fixed path, because the path is part of how a deployment finds
   its cache again — never one built from a temporary directory, a pid, a
   session id or the time.

Worker processes get both through their spawn environment (node_agent.py
`_spawn_worker`); programs that compile in their own process
(`__graft_entry__.py`, `bench.py`) call `apply()` before they import JAX.
"""

from __future__ import annotations

import os
import sys
from collections.abc import MutableMapping

DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_MIN_SECS_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def program_identity(env: MutableMapping[str, str] | None = None) -> None:
    """A program's text names no caller: a Mosaic kernel's payload holds its
    body's locations, ten frames of traceback each by JAX's default (a line
    shifted above a Pallas call was another cache key), with ONE its own
    frame in `ops/*.py` only (tests/test_v5e_compile.py). SET, as what a
    program is, in `env` and on an imported JAX's config. (No tracebacks at
    all would also strip the scopes a device trace is read by.)"""
    env = os.environ if env is None else env
    env["JAX_TRACEBACK_IN_LOCATIONS_LIMIT"] = "1"
    if env is os.environ and "jax" in sys.modules:
        import jax

        jax.config.update("jax_traceback_in_locations_limit", 1)


def apply(env: MutableMapping[str, str] | None = None) -> str:
    """Apply the module's rule to `env` (default: this process's
    environment). Returns the directory, "" where there is none.

    In the default directory every program is kept, however quickly it
    compiled: with JAX's one-second floor, a program that compiles in about
    a second is written by some runs and not by others, and a warm start
    never settles."""
    env = os.environ if env is None else env
    program_identity(env)  # wherever the cache is, and where there is none
    path = env.get(DIR_ENV)
    if path:
        return path
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return ""
    path = os.path.join(_CHECKOUT, ".jax_cache")
    env[DIR_ENV] = path
    env.setdefault(_MIN_SECS_ENV, "0")
    if env is os.environ and "jax" in sys.modules:
        # JAX read its environment at import; tell the live config too.
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(env[_MIN_SECS_ENV]))
    return path


def lists_dir() -> str:
    """Where a start leaves the list of the serving programs it asked for,
    for the next start to build ahead from (`llm/programs.py`): `programs/`
    under the directory THIS process's JAX keeps its compiled programs in,
    beside the entries the list's programs are read from; "" where it keeps
    none (the CPU rule above: no cache, no list)."""
    jax = sys.modules.get("jax")
    path = (os.environ.get(DIR_ENV) if jax is None
            else jax.config.jax_compilation_cache_dir)
    return os.path.join(path, "programs") if path else ""


def entries(path: str) -> int:
    """Number of compiled programs stored under `path` (0 if absent)."""
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except FileNotFoundError:
        return 0
