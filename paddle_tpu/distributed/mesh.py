"""Global device mesh management.

Parity target: the reference's ring registry
(platform/collective_helper.h:71 NCCLCommContext keyed by ring_id) and
the 4-D hybrid topology (fleet/base/topology.py:36 CommunicateTopology).

TPU-native design: ONE `jax.sharding.Mesh` over all devices with named
axes — the standard axis set is (dp, pp, sharding, mp, sp). A "process
group" is a subset of mesh axis names; collectives lower to XLA
collectives over those axes. ring_id ≙ axis-name tuple; comm init ops ≙
mesh construction (no rendezvous needed: XLA/PJRT handles ICI/DCN
wiring)."""
from __future__ import annotations

import threading

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_lock = threading.Lock()
_global_mesh = None
_group_counter = [0]
_groups = {}

STANDARD_AXES = ("dp", "pp", "sharding", "mp", "sp")


def build_mesh(axes: dict, devices=None) -> Mesh:
    """axes: ordered {name: size}. Sizes must multiply to #devices (a
    trailing -1 is inferred)."""
    devices = devices if devices is not None else jax.devices()
    names = list(axes.keys())
    sizes = list(axes.values())
    n = len(devices)
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total != n:
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total} "
                         f"devices, have {n}")
    arr = np.array(devices[:total]).reshape(sizes)
    return Mesh(arr, names)


def set_mesh(mesh: Mesh):
    global _global_mesh
    with _lock:
        _global_mesh = mesh
    return mesh


def get_mesh() -> Mesh | None:
    return _global_mesh


def ensure_mesh(**axes) -> Mesh:
    global _global_mesh
    with _lock:
        if _global_mesh is None:
            if not axes:
                axes = {"dp": len(jax.devices())}
            _global_mesh = build_mesh(axes)
        return _global_mesh


def default_mesh() -> Mesh:
    return ensure_mesh()


class Group:
    """A communicator = set of mesh axis names (ring_id analog)."""

    def __init__(self, gid, axis_names, ranks=None, nranks=None):
        self.id = gid
        self.axis_names = tuple(axis_names)
        self.ranks = ranks or []
        self._nranks = nranks

    @property
    def nranks(self):
        if self._nranks is not None:
            return self._nranks
        mesh = get_mesh()
        if mesh is None:
            return max(len(self.ranks), 1)
        n = 1
        for a in self.axis_names:
            if a in mesh.shape:
                n *= mesh.shape[a]
        return n

    @property
    def rank(self):
        from .env import get_rank

        return get_rank() if self.ranks == [] else (
            self.ranks.index(get_rank()) if get_rank() in self.ranks else -1)

    def is_member(self):
        return True

    def __repr__(self):
        return f"Group(id={self.id}, axes={self.axis_names})"


_WORLD = Group(0, ("dp",))


def world_group():
    mesh = get_mesh()
    if mesh is not None:
        _WORLD.axis_names = tuple(mesh.axis_names)
    return _WORLD


def new_group_for_axes(axis_names, ranks=None):
    with _lock:
        _group_counter[0] += 1
        g = Group(_group_counter[0], axis_names, ranks=ranks or [])
        _groups[g.id] = g
        return g


def get_group(gid):
    if gid == 0:
        return world_group()
    return _groups.get(gid)


def shard_map_compat(body, mesh, in_specs, out_specs):
    """The shard_map island builder ring attention and linalg.dist
    share: replication checking off, because their bodies carry
    collectives whose varying-ness the checker cannot prove."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def spec(*axes) -> PartitionSpec:
    return PartitionSpec(*axes)


def named_sharding(partition_spec, mesh=None) -> NamedSharding:
    mesh = mesh or default_mesh()
    return NamedSharding(mesh, partition_spec)
