"""Mesh registry: named, validated device-mesh topologies.

Every launcher resolves its mesh here instead of hand-building shapes:

  * ``debug``      — 2x2 (data, model), CPU integration tests under
                     ``--xla_force_host_platform_device_count``.
  * ``single-host``— 4x2 (data, model), one 8-accelerator host.
  * ``pod``        — 16x16 (data, model), one pod slice.
  * ``multi-pod``  — 2x16x16 (pod, data, model).

``make_mesh(name, data_parallel=..., model_parallel=...)`` resolves a spec,
applies axis-size overrides, validates the result against
``jax.device_count()`` (with an explicit ``devices=`` override for tests
that carve a mesh out of a larger forced-host-device pool), and builds the
Mesh.  Everything is functions — importing this module never touches jax
device state.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """A named mesh topology (validated lazily, at build time)."""
    name: str
    shape: Tuple[int, ...]
    axes: Tuple[str, ...]
    description: str = ""

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)

    @property
    def num_devices(self) -> int:
        return int(np.prod(self.shape))

    def with_sizes(self, *, data_parallel: Optional[int] = None,
                   model_parallel: Optional[int] = None,
                   time_parallel: Optional[int] = None) -> "MeshSpec":
        """Override the data/model/time axis sizes (None keeps the default)."""
        sizes = dict(zip(self.axes, self.shape))
        if data_parallel:
            if "data" not in sizes:
                raise ValueError(f"mesh '{self.name}' has no 'data' axis")
            sizes["data"] = data_parallel
        if model_parallel:
            if "model" not in sizes:
                raise ValueError(f"mesh '{self.name}' has no 'model' axis")
            sizes["model"] = model_parallel
        if time_parallel:
            if "time" not in sizes:
                raise ValueError(
                    f"mesh '{self.name}' has no 'time' axis; pick a "
                    f"*-time mesh ({', '.join(time_mesh_names())}) to "
                    f"shard solve windows")
            sizes["time"] = time_parallel
        return dataclasses.replace(
            self, shape=tuple(sizes[a] for a in self.axes))

    def build(self, *, devices: Optional[Sequence] = None) -> Mesh:
        """Validate against the available devices and build the Mesh.

        devices: explicit device list override (tests carving a small mesh
                 out of a forced host-device pool); defaults to
                 ``jax.devices()``.
        """
        n = self.num_devices
        if devices is not None:
            devs = list(devices)
            if len(devs) < n:
                raise ValueError(
                    f"mesh '{self.name}' {dict(zip(self.axes, self.shape))} "
                    f"needs {n} devices but only {len(devs)} were given")
            return Mesh(np.asarray(devs[:n]).reshape(self.shape), self.axes)
        avail = jax.device_count()
        if avail < n:
            raise ValueError(
                f"mesh '{self.name}' {dict(zip(self.axes, self.shape))} "
                f"needs {n} devices but jax.device_count()={avail}; pick a "
                f"smaller registered mesh ({', '.join(mesh_names())}), "
                f"override --data-parallel/--model-parallel"
                f"{'/--time-parallel' if 'time' in self.axes else ''}, or "
                f"force host devices with XLA_FLAGS=--xla_force_host_"
                f"platform_device_count={n}")
        # Auto axes: the serving stack shards through with_sharding_constraint
        # and NamedSharding, which only resolve against Auto mesh axes
        return jax.make_mesh(self.shape, self.axes,
                             axis_types=(AxisType.Auto,) * len(self.axes))


_REGISTRY: Dict[str, MeshSpec] = {}


def register_mesh(spec: MeshSpec) -> MeshSpec:
    _REGISTRY[spec.name] = spec
    return spec


def get_mesh_spec(name: str) -> MeshSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown mesh {name!r}; registered: "
                       f"{mesh_names()}") from None


def mesh_names():
    return sorted(_REGISTRY)


def time_mesh_names():
    """Registered meshes carrying a 'time' axis (window sharding)."""
    return sorted(n for n, s in _REGISTRY.items() if "time" in s.axes)


def make_mesh(name: str = "debug", *, data_parallel: Optional[int] = None,
              model_parallel: Optional[int] = None,
              time_parallel: Optional[int] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Resolve a registered mesh by name, apply axis-size overrides,
    validate against the device count, and build it."""
    spec = get_mesh_spec(name).with_sizes(
        data_parallel=data_parallel, model_parallel=model_parallel,
        time_parallel=time_parallel)
    return spec.build(devices=devices)


register_mesh(MeshSpec("debug", (2, 2), ("data", "model"),
                       "CPU integration tests (forced host devices)"))
register_mesh(MeshSpec("single-host", (4, 2), ("data", "model"),
                       "one 8-accelerator host"))
register_mesh(MeshSpec("pod", (16, 16), ("data", "model"),
                       "one pod slice"))
register_mesh(MeshSpec("multi-pod", (2, 16, 16), ("pod", "data", "model"),
                       "two pod slices, FSDP over (pod, data)"))

# time-axis geometries: the solve window of ONE request shards over `time`
# (devices >> slots regime; see repro.sampling.Placement.window_spec)
register_mesh(MeshSpec("debug-time", (2, 2, 2), ("data", "time", "model"),
                       "CPU integration tests with window sharding "
                       "(8 forced host devices)"))
register_mesh(MeshSpec("single-host-time", (2, 2, 2),
                       ("data", "time", "model"),
                       "one 8-accelerator host, windows split two ways"))
register_mesh(MeshSpec("pod-time", (8, 2, 16), ("data", "time", "model"),
                       "one pod slice with window sharding"))


# -- legacy constructors (thin wrappers over the registry) -------------------

def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    return make_mesh("multi-pod" if multi_pod else "pod")


def make_debug_mesh(data: int = 2, model: int = 2) -> Mesh:
    """Small mesh for CPU integration tests (requires
    xla_force_host_platform_device_count >= data*model)."""
    return make_mesh("debug", data_parallel=data, model_parallel=model)
