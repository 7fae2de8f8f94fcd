"""Chaos coverage for the streaming shuffle (README "Data plane"): a
SIGKILLed map or reduce worker mid-exchange re-executes through the task
retry + dedup plane and the output stays byte-identical (shards are
tagged by producing map index, merges order by tag); a severed sim://
spill backend surfaces an attributed DataSpillError after the bounded
retry budget — never a hang; a healthy spill path round-trips shards
bitwise through the storage plane."""

import os
import signal
import threading
import time

import pytest

import ray_tpu
from ray_tpu import data as rd
from ray_tpu.data._internal import exchange as xch
from ray_tpu.exceptions import DataSpillError


def _shuffle_blocks(items, seed, n_blocks, limit_s=180.0):
    """The shuffled blocks, within a time limit of this call's own (a
    shuffle takes seconds under tier-1's load): the exchange's driver loop
    waits without end for a shard that is never made, and that has to fail
    the test here, not hold the whole run."""
    box = {}

    def run():
        try:
            refs = rd.from_items(items, parallelism=n_blocks).random_shuffle(
                seed=seed)._block_refs()
            box["blocks"] = ray_tpu.get(refs, timeout=limit_s)
        except BaseException as e:  # noqa: BLE001 - re-raised by the caller
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(limit_s)
    if t.is_alive():
        pytest.fail(f"shuffle still running after {limit_s:.0f}s; exchange "
                    f"stats {xch.exchange_stats()}")
    if "error" in box:
        raise box["error"]
    return box["blocks"]


def _leased_pid():
    for slot in ray_tpu._head.agent.workers.values():
        if slot.state == "leased" and slot.proc.poll() is None:
            return slot.proc.pid
    return None


def _kill_leased_worker_when(pred, killed, timeout=30.0):
    """Background chaos: once `pred()` holds, SIGKILL a leased worker."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        pid = _leased_pid() if pred() else None
        if pid is not None:
            os.kill(pid, signal.SIGKILL)
            killed["pid"] = pid
            return
        time.sleep(0.002)


def test_sigkill_map_worker_mid_shuffle_output_identical(ray_start_2cpu,
                                                         monkeypatch):
    """Kill a worker while the map wave is live: retries re-execute the
    lost maps, tagged shards land in the same merge slots, and the
    shuffled blocks match the no-chaos run byte for byte."""
    monkeypatch.setenv("RT_DATA_MAX_INFLIGHT_BLOCKS", "4")
    items = [os.urandom(1024) for _ in range(768)]
    expect = _shuffle_blocks(items, seed=7, n_blocks=24)

    xch.reset_exchange_stats()
    killed = {"pid": None}
    t = threading.Thread(
        target=_kill_leased_worker_when,
        args=(lambda: 1 <= xch.exchange_stats()["maps_done"] < 20, killed))
    t.start()
    try:
        got = _shuffle_blocks(items, seed=7, n_blocks=24)
    finally:
        t.join(timeout=60)
    assert killed["pid"] is not None, "chaos kill never fired"
    assert got == expect, "shuffle output changed under a map-worker kill"


def test_sigkill_reduce_worker_mid_shuffle_output_identical(ray_start_2cpu,
                                                            monkeypatch):
    """Kill a worker once reduce-side consolidations are in flight (small
    fan-in makes them plentiful and early): the re-executed merges see the
    same tagged inputs and the output is byte-identical."""
    monkeypatch.setenv("RT_DATA_REDUCE_FANIN", "2")
    monkeypatch.setenv("RT_DATA_MAX_INFLIGHT_BLOCKS", "4")
    items = [os.urandom(1024) for _ in range(768)]
    expect = _shuffle_blocks(items, seed=8, n_blocks=24)

    xch.reset_exchange_stats()
    killed = {"pid": None}
    t = threading.Thread(
        target=_kill_leased_worker_when,
        args=(lambda: xch.exchange_stats()["reduces_submitted"] >= 4, killed))
    t.start()
    try:
        got = _shuffle_blocks(items, seed=8, n_blocks=24)
    finally:
        t.join(timeout=60)
    assert killed["pid"] is not None, "chaos kill never fired"
    assert got == expect, "shuffle output changed under a reduce-worker kill"


def test_severed_spill_backend_attributed_error_no_hang(shutdown_only,
                                                        monkeypatch,
                                                        tmp_path):
    """Every spill write hits a severed sim:// backend: the exchange fails
    within the bounded retry budget with a DataSpillError naming the shard
    uri and partition — it must never hang the consumer."""
    monkeypatch.setenv("RT_DATA_SPILL_URI", "sim://" + str(tmp_path / "sp"))
    monkeypatch.setenv("RT_DATA_MEM_CAP_BYTES", "1")  # every merge spills
    monkeypatch.setenv("RT_DATA_REDUCE_FANIN", "2")
    monkeypatch.setenv("RT_SIM_STORAGE_SEVERED", "1")  # workers inherit
    ray_tpu.init(num_cpus=2)
    items = [os.urandom(256) for _ in range(64)]
    t0 = time.monotonic()
    with pytest.raises(Exception) as ei:
        _shuffle_blocks(items, seed=4, n_blocks=8)
    elapsed = time.monotonic() - t0
    assert elapsed < 120, f"severed spill took {elapsed:.0f}s to surface"
    err = ei.value
    cause = getattr(err, "cause", None) or err.__cause__
    attributed = isinstance(err, DataSpillError) or \
        isinstance(cause, DataSpillError) or "DataSpillError" in str(err)
    assert attributed, f"unattributed failure: {err!r}"
    assert "sim://" in str(err) or (cause and "sim://" in str(cause)), (
        f"error does not name the spill uri: {err}")


def test_spill_restore_roundtrip_bitwise(shutdown_only, monkeypatch,
                                         tmp_path):
    """Healthy sim:// spill path: a mem-cap-forced spill through the sim
    backend restores bitwise — the spilled run's blocks equal a no-spill
    run's blocks exactly, and restores clean up their backing files."""
    items = [os.urandom(512) for _ in range(128)]
    ray_tpu.init(num_cpus=2)
    try:
        expect = _shuffle_blocks(items, seed=6, n_blocks=8)
    finally:
        ray_tpu.shutdown()

    fs_root = str(tmp_path / "sp")
    monkeypatch.setenv("RT_DATA_SPILL_URI", "sim://" + fs_root)
    monkeypatch.setenv("RT_DATA_MEM_CAP_BYTES", "1")  # every merge spills
    monkeypatch.setenv("RT_DATA_REDUCE_FANIN", "2")
    ray_tpu.init(num_cpus=2)
    got = _shuffle_blocks(items, seed=6, n_blocks=8)
    assert got == expect, "spill+restore changed the shuffle output"
    leftovers = [f for _r, _d, fs in os.walk(fs_root) for f in fs]
    assert leftovers == [], f"restored shards not cleaned up: {leftovers}"
