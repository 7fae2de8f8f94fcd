"""Device mesh construction and sharding helpers.

Replaces (TPU-natively) the reference's process-group bootstrap
(python/ray/train/torch/config.py:66 _setup_torch_process_group — NCCL
rendezvous) and DDP/FSDP wrapping (train/torch/train_loop_utils.py:189):
instead of wrapping modules, we build one `jax.sharding.Mesh` whose named
axes carry every parallelism dimension, annotate arrays with PartitionSpecs,
and let XLA's GSPMD partitioner insert the ICI collectives.

Axis conventions (the scaling-book recipe):
    dp — data parallelism (batch dim; gradient psum)
    fsdp — parameter sharding a la ZeRO-3 (params gathered on use)
    tp — tensor parallelism (matmul output/head dim)
    sp — sequence/context parallelism (sequence dim; ring attention)
    pp — pipeline stages (lax.scan over stages or stage meshes)
    ep — expert parallelism (MoE expert dim; all_to_all routing)
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_ORDER = ("dp", "fsdp", "pp", "sp", "tp", "ep")


@dataclass(frozen=True)
class MeshConfig:
    """Degrees for each parallelism axis; -1 on one axis = use remaining
    devices. Axes of degree 1 still exist in the mesh (size-1 axes are free
    in XLA) so PartitionSpecs can always name them."""

    dp: int = -1
    fsdp: int = 1
    pp: int = 1
    sp: int = 1
    tp: int = 1
    ep: int = 1

    def resolve(self, n_devices: int) -> dict[str, int]:
        sizes = {a: getattr(self, a) for a in AXIS_ORDER}
        fixed = 1
        wild = None
        for a, s in sizes.items():
            if s == -1:
                if wild is not None:
                    raise ValueError("only one mesh axis may be -1")
                wild = a
            else:
                fixed *= s
        if wild is not None:
            if n_devices % fixed:
                raise ValueError(f"{n_devices} devices not divisible by {fixed}")
            sizes[wild] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(f"mesh {sizes} needs {fixed} devices, have {n_devices}")
        return sizes


def build_mesh(config: MeshConfig | None = None, devices=None) -> Mesh:
    """Build a Mesh over the given (default: all) devices.

    On a TPU, `jax.experimental.mesh_utils.create_device_mesh` lays the axes
    out along the physical torus so that ICI neighbours stay adjacent. Other
    platforms have no topology to follow: their devices are reshaped in
    order.
    """
    config = config or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    sizes = config.resolve(len(devices))
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def context_mesh_shape() -> dict[str, int]:
    """Axis sizes of the mesh in context, whichever way it was entered; {}
    when there is none.

    JAX 0.9.0 keeps two contexts that do not see each other:
    `jax.set_mesh(mesh)` sets the abstract mesh, the older `with mesh:` sets
    only the thread-local physical mesh, which has no public reader.
    `with_sharding_constraint` honours a bare PartitionSpec under either, so
    both are read here: a caller under `with mesh:` must not lose sequence
    parallelism without an error."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        from jax._src.mesh import thread_resources

        mesh = thread_resources.env.physical_mesh
    return dict(mesh.shape)


def local_mesh(n: int | None = None, axis: str = "dp") -> Mesh:
    """1-axis mesh over the first n local devices (tests, single-host)."""
    devices = jax.devices()[: n or len(jax.devices())]
    return Mesh(np.asarray(devices), (axis,))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharding(mesh: Mesh, *, batch_axes: tuple[str, ...] = ("dp", "fsdp"),
                  seq_axis: str | None = None) -> NamedSharding:
    """Batch sharded over the data axes; optionally sequence over sp.
    For [batch, seq, ...] inputs."""
    if seq_axis:
        return NamedSharding(mesh, P(batch_axes, seq_axis))
    return NamedSharding(mesh, P(batch_axes))


def shard_params(params, specs, mesh: Mesh):
    """Place a parameter pytree according to a matching PartitionSpec pytree
    (device_put with NamedShardings — the GSPMD analogue of FSDP/DeepSpeed
    parameter sharding, reference train_loop_utils.py:189)."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs,
        is_leaf=lambda x: x is None,
    )


def spec_tree_like(params, fn):
    """Build a PartitionSpec tree by calling fn(path, leaf) over params."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [fn(tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path), leaf)
             for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)
