"""TPU accelerator detection — TPU chips are first-class schedulable resources.

Parity target: reference python/ray/_private/accelerators/tpu.py:109
(TPUAcceleratorManager — detects chips via /dev/accel* & /dev/vfio
tpu.py:135-150, narrows a process to its chips with TPU_VISIBLE_CHIPS, knows
pod topology, e.g. get_num_workers_in_current_tpu_pod tpu.py:312). Unlike the
reference — where TPU support is one plugin among many — this runtime treats
"TPU" like the reference treats GPU, and additionally advertises slice-level
gang resources ("TPU-<accel>-<topology>-head") so pod-scale jobs can be
placed atomically.

This module detects chips and says what environment narrows a process to a
given set of them (`worker_env`); which worker holds which chip is booked by
the node agent (node_agent.py `_spawn_worker`), because the chip is returned
when that worker's process exits.
"""

from __future__ import annotations

import glob
import os
import re
from collections.abc import Sequence

TPU_RESOURCE = "TPU"

#: libtpu's per-process topology for a subset of one host's chips (the
#: reference's TPU_CHIPS_PER_HOST_BOUNDS table, tpu.py:30-40, under the names
#: the installed libtpu reads).
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1"}
#: First port of the per-process libtpu runtime service; a process narrowed
#: to a subset listens on this plus its first chip id so that several
#: processes on one host do not collide.
_PROCESS_PORT_BASE = 8476


def num_tpu_chips() -> int:
    """Detect the number of TPU chips on this host."""
    env = os.environ.get("RT_NUM_TPUS") or os.environ.get("TPU_CHIPS")
    if env:
        return int(env)
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        return len([c for c in visible.split(",") if c.strip()])
    # Device-file probing, same sources as the reference (tpu.py:135-150).
    n = len(glob.glob("/dev/accel*"))
    if n == 0 and os.path.isdir("/dev/vfio"):
        n = len([f for f in os.listdir("/dev/vfio") if f != "vfio"])
    return n


def open_chip_files() -> list[str]:
    """The TPU device files this process holds open right now
    (`/dev/accel*`, `/dev/vfio/N`), read from `/proc/self/fd`: which chips
    the runtime inside this process really opened, as distinct from which
    it was told to open. Empty before JAX has started its TPU backend."""
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:  # the fd of this listing itself, closed since
            continue
        if re.fullmatch(r"/dev/(accel\d+|vfio/\d+)", target):
            held.add(target)
    return sorted(held)


def tpu_chip_ids(n: int) -> list[int]:
    """The ids of this host's `n` schedulable chips: what TPU_VISIBLE_CHIPS
    already narrows this process to when it names exactly n, else 0..n-1."""
    visible = [int(c) for c in
               (os.environ.get("TPU_VISIBLE_CHIPS") or "").split(",")
               if c.strip()]
    return visible if len(visible) == n else list(range(n))


def worker_env(chips: Sequence[int], host_chips: int) -> dict[str, str]:
    """Environment of a worker process granted `chips` (ids) on a host with
    `host_chips` schedulable chips. No chip: the process is held to the CPU
    whatever the host environment says — a chip belongs to one process, so a
    worker that was granted none must never open one by importing JAX. A
    proper subset: libtpu is told the chips, the process-local topology and
    a port of its own. The whole host needs no narrowing."""
    if not chips:
        return {"JAX_PLATFORMS": "cpu"}
    env = {"TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips)}
    if len(chips) < host_chips:
        bounds = _CHIP_BOUNDS.get(len(chips))
        if bounds is None:
            raise ValueError(
                f"TPU: {len(chips)} of a {host_chips}-chip host is not a "
                f"shape libtpu can open (whole host, or one of "
                f"{sorted(_CHIP_BOUNDS)})")
        port = _PROCESS_PORT_BASE + chips[0]
        env.update(
            TPU_CHIPS_PER_PROCESS_BOUNDS=bounds,
            TPU_PROCESS_BOUNDS="1,1,1",
            # The same two under the names hosts still set (a v5e host
            # comes with TPU_CHIPS_PER_HOST_BOUNDS=2,2,1): left alone they
            # would describe the whole host to a process that sees a part.
            TPU_CHIPS_PER_HOST_BOUNDS=bounds,
            TPU_HOST_BOUNDS="1,1,1",
            TPU_PROCESS_ADDRESSES=f"localhost:{port}",
            TPU_PROCESS_PORT=str(port),
            CLOUD_TPU_TASK_ID="0",
        )
    return env


def tpu_generation() -> str | None:
    """e.g. 'v5e' | 'v4' — from env (GKE sets TPU_ACCELERATOR_TYPE)."""
    accel = os.environ.get("TPU_ACCELERATOR_TYPE")  # e.g. "v5litepod-16"
    if accel:
        return accel.split("-")[0].replace("litepod", "5e").replace("v5lite", "v5e")
    return None


def tpu_pod_resources() -> dict[str, float]:
    """Extra pod-topology resources for this host (slice head marker etc.).
    Mirrors the reference's `TPU-{accel}-head` custom resource that lets a
    single task gang-own a pod slice (tpu.py get_current_pod_name/worker
    count)."""
    out: dict[str, float] = {}
    accel = os.environ.get("TPU_ACCELERATOR_TYPE")
    worker_id = os.environ.get("TPU_WORKER_ID")
    if accel and (worker_id is None or worker_id == "0"):
        out[f"TPU-{accel}-head"] = 1.0
    return out


def host_resources(num_cpus: float | None = None, num_tpus: float | None = None) -> dict[str, float]:
    r: dict[str, float] = {}
    r["CPU"] = float(num_cpus) if num_cpus is not None else float(os.cpu_count() or 1)
    chips = num_tpus if num_tpus is not None else num_tpu_chips()
    if chips:
        r[TPU_RESOURCE] = float(chips)
        r.update(tpu_pod_resources())
    return r
