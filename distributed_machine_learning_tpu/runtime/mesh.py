"""Device-mesh construction.

The reference's "mesh" is a gloo process group over TCP
(``dist.init_process_group`` — ``part2/2a/main.py:197``).  Here the unit
of parallelism is a ``jax.sharding.Mesh`` over TPU chips; the data axis
(``"batch"``) plays the role of the gloo world, with XLA collectives
riding ICI.  The mesh is 1-D for the reference's data-parallel-only
capability surface (SURVEY.md §2.3) but constructed through a general
helper so additional axes (model/pipeline/sequence) slot in without
touching callers.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

BATCH_AXIS = "batch"

# The state layouts a checkpoint can be saved under (and resharded
# between worlds within): replicated data parallelism, ZeRO-1
# (params replicated / momentum sharded), and ZeRO-3/FSDP (both
# sharded).  The flat-shard layouts pad their vectors to a multiple of
# the world size, which is exactly what a world-size change must redo.
SHARD_LAYOUTS = ("dp", "zero1", "fsdp")


def padded_len(n_elems: int, world: int) -> int:
    """Length of the flat param/momentum vectors after padding to a
    multiple of ``world`` — the canonical definition shared by the
    flat-shard schemes (``parallel/fsdp.py``, ``parallel/zero1.py``)
    and the checkpoint resharder, so partition boundaries recompute
    identically everywhere."""
    if world < 1:
        raise ValueError(f"world must be >= 1, got {world}")
    return -(-n_elems // world) * world


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """How a training state is laid out across a data-parallel world —
    the metadata a checkpoint must carry for a restore onto a
    *different* world size to be possible.

    ``layout``: one of :data:`SHARD_LAYOUTS`.  ``world``: the data-axis
    size the state was built for.  ``n_elems``: the unpadded length of
    the flat param/momentum vectors (zero1/fsdp — the *logical* array a
    reshard preserves bit-for-bit; None for dp, whose leaves carry no
    padding).
    """

    layout: str
    world: int
    n_elems: int | None = None

    def __post_init__(self):
        if self.layout not in SHARD_LAYOUTS:
            raise ValueError(
                f"unknown layout {self.layout!r}; known: {SHARD_LAYOUTS}"
            )
        if self.world < 1:
            raise ValueError(f"world must be >= 1, got {self.world}")
        if self.layout != "dp" and self.n_elems is None:
            raise ValueError(
                f"layout {self.layout!r} needs n_elems (the unpadded "
                "flat length) to recompute partition boundaries"
            )

    @property
    def padded(self) -> int | None:
        """The padded flat length under this spec, or None for dp."""
        return None if self.n_elems is None else padded_len(
            self.n_elems, self.world
        )

    def with_world(self, world: int) -> "ShardSpec":
        """The same layout re-laid-out for a different world size."""
        return dataclasses.replace(self, world=world)

    def as_dict(self) -> dict:
        return {"layout": self.layout, "world": self.world,
                "n_elems": self.n_elems}

    @classmethod
    def from_dict(cls, payload: dict) -> "ShardSpec":
        return cls(
            layout=str(payload["layout"]), world=int(payload["world"]),
            n_elems=(None if payload.get("n_elems") is None
                     else int(payload["n_elems"])),
        )


def repad_flat(flat: np.ndarray, n_elems: int, world: int) -> np.ndarray:
    """Re-lay-out one flat padded vector for a new world size: keep the
    logical prefix ``flat[:n_elems]`` bit-for-bit, recompute the padded
    length for ``world``, and zero-fill the new tail.  The whole of a
    zero1/fsdp reshard is this, applied per flat leaf — padding is the
    only world-size-dependent part of the layout."""
    flat = np.asarray(flat)
    if flat.ndim != 1:
        raise ValueError(f"expected a flat vector, got shape {flat.shape}")
    if flat.shape[0] < n_elems:
        raise ValueError(
            f"flat vector of {flat.shape[0]} elements cannot hold "
            f"n_elems={n_elems} logical values"
        )
    out = np.zeros((padded_len(n_elems, world),), dtype=flat.dtype)
    out[:n_elems] = flat[:n_elems]
    return out


def shard_map_no_check(f, *, mesh, in_specs, out_specs, manual_axes=None):
    """``jax.shard_map`` with replication checking off (``check_vma=False``).

    ``manual_axes``: restrict manual sharding to a subset of mesh axes
    (jax's ``axis_names``); the rest stay under automatic GSPMD
    propagation — how the 3-D step composes a manual ppermute pipeline
    with compiler-derived tensor/data parallelism
    (``parallel/parallel3d.py``).  None (default) = fully manual.
    """
    kwargs = {} if manual_axes is None else {"axis_names": frozenset(manual_axes)}
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False, **kwargs,
    )


def replicate(tree, mesh: Mesh):
    """Commit every leaf of ``tree`` to all of ``mesh``'s devices (the
    placement a ``shard_map`` in_spec of ``P()`` expects).  A fresh or
    restored state otherwise sits on the default device until the first
    donated step moves it — after which the second step sees
    differently-placed inputs and compiles the whole program again —
    and a device-0-COMMITTED state next to mesh-sharded batches is a
    hard error."""
    from distributed_machine_learning_tpu.telemetry import startup

    with startup.place_state(tree, mesh):
        return jax.device_put(tree, NamedSharding(mesh, PartitionSpec()))


def ensure_host_devices(n: int = 8) -> None:
    """Put ``--xla_force_host_platform_device_count=n`` into XLA_FLAGS
    if no device-count flag is present yet.

    MUST run before the CPU client spins up (the first ``jax.devices()``
    call) — after that the flag is ignored.  The ONE copy of the dance
    the virtual-mesh entrypoints share (the dmlcheck CLI, the overlap
    audit's ``--cpu-mesh`` path), so the device count and the
    ordering invariant cannot drift between them.  tests/conftest.py
    keeps its own inline copy deliberately: it must mutate the env
    before importing ANYTHING from this package."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def make_mesh(
    num_devices: int | None = None,
    axis_names: tuple[str, ...] = (BATCH_AXIS,),
    axis_shape: tuple[int, ...] | None = None,
    devices=None,
) -> Mesh:
    """Build a Mesh over (a prefix of) the available devices.

    With defaults: a 1-D data-parallel mesh over all devices.  Pass
    ``axis_names``/``axis_shape`` for multi-axis layouts, e.g.
    ``axis_names=("batch", "model"), axis_shape=(4, 2)``.
    """
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} devices, have {len(devices)}"
            )
        devices = devices[:num_devices]
    if axis_shape is None:
        axis_shape = (len(devices),) + (1,) * (len(axis_names) - 1)
    if int(np.prod(axis_shape)) != len(devices):
        raise ValueError(f"axis_shape {axis_shape} != {len(devices)} devices")
    mesh_devices = np.asarray(devices).reshape(axis_shape)
    return Mesh(mesh_devices, axis_names)
