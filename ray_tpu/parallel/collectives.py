"""Device-tier collectives: in-program XLA collectives over mesh axes.

The TPU-native replacement for the reference's NCCL groups
(util/collective/collective_group/nccl_collective_group.py,
experimental/channel/nccl_group.py:22): instead of out-of-band process
groups, collective math is expressed inside compiled programs with
`jax.lax` primitives under `shard_map`, and XLA lowers them to ICI
transfers. These helpers wrap the common patterns so library code (Train
learners, ring attention) doesn't repeat shard_map boilerplate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """jax.shard_map with the static-replication check relaxed by default:
    collective-heavy bodies (all_gather -> replicated out) routinely defeat
    the inference and the runtime sharding is still checked."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def psum(x, axis_name: str):
    """Inside shard_map/pjit: sum across a mesh axis (ICI allreduce)."""
    return jax.lax.psum(x, axis_name)


def pmean(x, axis_name: str):
    return jax.lax.pmean(x, axis_name)


def all_gather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def ppermute_ring(x, axis_name: str, mesh: Mesh, shift: int = 1):
    """Rotate shards one step around the axis ring (the primitive under
    ring attention / pipeline handoff)."""
    n = mesh.shape[axis_name]
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int):
    return jax.lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=True)


def mesh_allreduce(mesh: Mesh, x, axis_name: str = "dp"):
    """Whole-array allreduce over one mesh axis, runnable from host code:
    jit(shard_map(psum)). For gradient sync when not already inside a pjit
    program (the common JaxTrainer DP path runs psum inside the train step
    instead — this is the standalone utility)."""
    spec = P(axis_name)
    fn = shard_map(
        functools.partial(jax.lax.psum, axis_name=axis_name),
        mesh=mesh, in_specs=spec, out_specs=P())

    sharded = jax.device_put(x, NamedSharding(mesh, spec))
    return jax.jit(fn)(sharded)
