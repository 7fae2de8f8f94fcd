"""Who holds a chip, and where compiled programs are kept (no chip needed).

A chip belongs to one process. The node agent books specific chips to a
worker that holds the `TPU` resource and takes them back when the process
has exited; a worker granted none is held to the CPU whatever the host
environment says. The compile cache has one setter.
"""

import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu._private import accelerators, compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------- the environment
def test_worker_env_rules():
    # No chip: the CPU, and nothing about TPUs.
    assert accelerators.worker_env([], 4) == {"JAX_PLATFORMS": "cpu"}
    assert accelerators.worker_env([], 0) == {"JAX_PLATFORMS": "cpu"}
    # The whole host needs no narrowing beyond naming its chips.
    assert accelerators.worker_env([0], 1) == {"TPU_VISIBLE_CHIPS": "0"}
    assert accelerators.worker_env([0, 1, 2, 3], 4) == {
        "TPU_VISIBLE_CHIPS": "0,1,2,3"}
    # A proper subset: process-local topology and a port of its own.
    a, b = accelerators.worker_env([2], 4), accelerators.worker_env([3], 4)
    assert a["TPU_VISIBLE_CHIPS"] == "2" and b["TPU_VISIBLE_CHIPS"] == "3"
    assert a["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert a["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert a["TPU_PROCESS_PORT"] != b["TPU_PROCESS_PORT"]
    assert a["TPU_PROCESS_ADDRESSES"].endswith(":" + a["TPU_PROCESS_PORT"])
    assert "JAX_PLATFORMS" not in a
    assert accelerators.worker_env([0, 1], 4)[
        "TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    with pytest.raises(ValueError, match="not a shape libtpu can open"):
        accelerators.worker_env([0, 1, 2], 4)


def test_chip_ids_follow_visible_chips(monkeypatch):
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    assert accelerators.tpu_chip_ids(4) == [0, 1, 2, 3]
    assert accelerators.tpu_chip_ids(0) == []
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
    assert accelerators.tpu_chip_ids(2) == [2, 3]
    assert accelerators.tpu_chip_ids(4) == [0, 1, 2, 3]  # count disagrees


def test_open_chip_files_reads_what_the_process_holds(monkeypatch):
    """The process-side fact `chip_smoke.py` tells replicas' chips apart by:
    the device files in /proc/self/fd (the fd table of a process with one
    v5e chip open, as seen on the chip machine)."""
    fds = {"3": "/tmp/libtpu_lockfile", "13": "/dev/vfio/vfio",
           "14": "/dev/vfio/2", "15": "anon_inode:[vfio-device]",
           "16": "/dev/accel1", "17": "/dev/vfio/2"}
    monkeypatch.setattr(accelerators.os, "listdir",
                        lambda path: [*fds, "99"])

    def readlink(path):
        try:
            return fds[path.rsplit("/", 1)[1]]
        except KeyError:
            raise FileNotFoundError(path) from None

    monkeypatch.setattr(accelerators.os, "readlink", readlink)
    assert accelerators.open_chip_files() == ["/dev/accel1", "/dev/vfio/2"]


@ray_tpu.remote(num_cpus=0)
class _EnvProbe:
    def env(self):
        return {k: os.environ.get(k) for k in (
            "JAX_PLATFORMS", "TPU_VISIBLE_CHIPS", "TPU_PROCESS_PORT",
            "JAX_COMPILATION_CACHE_DIR")} | {"pid": os.getpid()}


def _env(actor):
    return ray_tpu.get(actor.env.remote(), timeout=60)


def test_spawn_env_follows_the_tpu_resource(shutdown_only, monkeypatch):
    """On a node advertising 4 chips: a worker without `TPU` is held to the
    CPU even though the host environment names the TPU; `num_tpus=1` actors
    get disjoint chips; a killed actor's chip is handed out again."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    ray_tpu.init(num_cpus=2, num_tpus=4)

    with pytest.raises(ValueError, match="whole number of chips"):
        _EnvProbe.options(num_tpus=0.5).remote()

    plain = _env(_EnvProbe.remote())
    assert plain["JAX_PLATFORMS"] == "cpu"
    assert plain["TPU_VISIBLE_CHIPS"] is None
    assert plain["JAX_COMPILATION_CACHE_DIR"] is None  # CPU keeps no cache

    holders = [_EnvProbe.options(num_tpus=1).remote() for _ in range(4)]
    envs = [_env(a) for a in holders]
    assert sorted(e["TPU_VISIBLE_CHIPS"] for e in envs) == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    assert len({e["pid"] for e in envs}) == 4
    for e in envs:
        assert e["JAX_PLATFORMS"] == "tpu"  # the host's choice is kept
        assert e["JAX_COMPILATION_CACHE_DIR"] == os.path.join(
            REPO, ".jax_cache")

    # Every chip is held. Kill one holder: its chip, and only its chip, is
    # free for the next `num_tpus=1` actor.
    ray_tpu.kill(holders[2])
    again = _env(_EnvProbe.options(num_tpus=1).remote())
    assert again["TPU_VISIBLE_CHIPS"] == envs[2]["TPU_VISIBLE_CHIPS"]
    assert again["pid"] != envs[2]["pid"]


def test_whole_host_grant_sets_no_process_bounds(shutdown_only):
    ray_tpu.init(num_cpus=1, num_tpus=2)
    e = _env(_EnvProbe.options(num_tpus=2).remote())
    assert e["TPU_VISIBLE_CHIPS"] == "0,1"
    assert e["TPU_PROCESS_PORT"] is None


# ---------------------------------------------------------- the compile cache
#: What `compile_cache.program_identity` sets, whatever rule places the cache.
IDENTITY = {"JAX_TRACEBACK_IN_LOCATIONS_LIMIT": "1"}


def test_compile_cache_rule():
    # Set outside the program: used as it is; nothing else about the cache
    # is set.
    env = {"JAX_COMPILATION_CACHE_DIR": "/some/dir"}
    assert compile_cache.apply(env) == "/some/dir"
    assert env == {"JAX_COMPILATION_CACHE_DIR": "/some/dir", **IDENTITY}
    # Set outside wins over the CPU rule too.
    env = {"JAX_COMPILATION_CACHE_DIR": "/some/dir", "JAX_PLATFORMS": "cpu"}
    assert compile_cache.apply(env) == "/some/dir"
    assert len(env) == 3
    # Unset: <checkout>/.jax_cache — a fixed path, where everything is kept.
    env = {}
    assert compile_cache.apply(env) == os.path.join(REPO, ".jax_cache")
    assert env == {
        "JAX_COMPILATION_CACHE_DIR": os.path.join(REPO, ".jax_cache"),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0", **IDENTITY}
    # Unset and held to the CPU: no cache.
    env = {"JAX_PLATFORMS": "cpu"}
    assert compile_cache.apply(env) == ""
    assert "JAX_COMPILATION_CACHE_DIR" not in env


@pytest.mark.parametrize("env", [
    {"JAX_COMPILATION_CACHE_DIR": "/some/dir"}, {"JAX_PLATFORMS": "cpu"}, {}],
    ids=["placed-outside", "held-to-the-cpu", "the-checkout's"])
def test_a_program_names_no_caller_under_every_rule(env, monkeypatch):
    """Under each of `apply`'s three rules the spawn environment says that a
    location in a program's text is one frame, and says it over whatever it
    said before: it is SET, being part of what a program is. A process that
    has JAX imported is told on its live config as well, and `apply` on a
    mapping that is not this process's environment leaves that alone."""
    import jax

    var, name = ("JAX_TRACEBACK_IN_LOCATIONS_LIMIT",
                 "jax_traceback_in_locations_limit")
    env[var] = "10"
    monkeypatch.setenv(var, "10")
    jax.config.update(name, 10)
    try:
        compile_cache.apply(env)
        assert env[var] == "1"
        assert getattr(jax.config, name) == 10  # `env` is not os.environ
        compile_cache.program_identity()
        assert getattr(jax.config, name) == 1
        assert os.environ[var] == "1"
    finally:
        jax.config.update(name, 1)


def test_compile_cache_has_one_setter():
    """No other module of the tree names the cache directory."""
    hits = subprocess.run(
        ["grep", "-rlE", "compilation_cache_dir|COMPILATION_CACHE_DIR",
         "--include=*.py", "ray_tpu", "bench.py", "__graft_entry__.py",
         "chip_smoke.py"],
        cwd=REPO, capture_output=True, text=True).stdout.split()
    assert hits == ["ray_tpu/_private/compile_cache.py"]


def test_building_the_serving_app_imports_no_jax():
    """A process that builds the OpenAI application and never runs it (the
    benchmark's driver, which refuses its own run otherwise: a chip belongs
    to one process) imports the engine, the pipeline and the derivation of
    the model's shape without importing JAX."""
    code = ("import sys; import ray_tpu.llm.openai, ray_tpu.llm.pipeline; "
            "from ray_tpu.llm.engine import model_config; "
            "assert model_config.__module__ == 'ray_tpu.models.published'; "
            "sys.exit('jax' in sys.modules)")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr


# ------------------------------------------------------------- chip_smoke.py
@pytest.mark.parametrize("platforms", ["cpu", None])
def test_chip_smoke_refuses_a_host_without_tpu(platforms):
    """Held to the CPU, or on a host with no TPU device files (this
    sandbox), the smoke exits non-zero within seconds, says why, and prints
    no result."""
    if platforms is None and accelerators.num_tpu_chips():
        pytest.skip("this host has TPU device files")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    if platforms:
        env["JAX_PLATFORMS"] = platforms
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=60)
    assert time.monotonic() - t0 < 30
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout.strip() == ""
