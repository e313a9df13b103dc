"""ProcessMesh — the device topology object of the auto-parallel API.

TPU-native analog of the reference `phi/core/distributed/auto_parallel/
process_mesh.h:34` + python `paddle.distributed.ProcessMesh`. Here a mesh is a
view over `jax.devices()`: `to_jax_mesh()` yields the `jax.sharding.Mesh` that
GSPMD partitions over (ICI within a slice, DCN across slices — XLA routes by
the device order given).
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import numpy as np

_global_mesh: Optional["ProcessMesh"] = None


class ProcessMesh:
    def __init__(self, mesh: Sequence, dim_names: Optional[List[str]] = None,
                 shape=None, process_ids=None):
        if shape is not None and process_ids is not None:
            arr = np.asarray(process_ids, dtype=np.int64).reshape(shape)
        else:
            arr = np.asarray(mesh, dtype=np.int64)
        if dim_names is None:
            dim_names = [f"d{i}" for i in range(arr.ndim)]
        if len(dim_names) != arr.ndim:
            raise ValueError(
                f"dim_names {dim_names} does not match mesh ndim {arr.ndim}")
        if len(set(dim_names)) != len(dim_names):
            raise ValueError(f"duplicate dim_names: {dim_names}")
        self._mesh = arr
        self._dim_names = list(dim_names)

    # -- reference-parity accessors ----------------------------------------
    @property
    def mesh(self) -> np.ndarray:
        return self._mesh

    @property
    def shape(self) -> List[int]:
        return list(self._mesh.shape)

    @property
    def ndim(self) -> int:
        return self._mesh.ndim

    @property
    def size(self) -> int:
        return int(self._mesh.size)

    @property
    def process_ids(self) -> List[int]:
        return [int(x) for x in self._mesh.flatten()]

    @property
    def dim_names(self) -> List[str]:
        return list(self._dim_names)

    def get_dim_size(self, dim_name: str) -> int:
        return self._mesh.shape[self._dim_names.index(dim_name)]

    def get_rank_by_dim_and_process_id(self, dim_name, process_id):
        axis = self._dim_names.index(dim_name)
        where = np.argwhere(self._mesh == process_id)
        if where.size == 0:
            return -1
        return int(where[0][axis])

    def get_mesh_with_dim(self, dim_name, index=None):
        """Sub-mesh obtained by moving `dim_name` first (and optionally
        indexing it) — reference `ProcessMesh.get_mesh_with_dim`."""
        axis = self._dim_names.index(dim_name)
        perm = [axis] + [i for i in range(self.ndim) if i != axis]
        names = [self._dim_names[i] for i in perm]
        moved = np.transpose(self._mesh, perm)
        if index is not None:
            return ProcessMesh(moved[index], names[1:])
        return ProcessMesh(moved, names)

    def __getitem__(self, index):
        sub = self._mesh[index]
        if sub.ndim == self.ndim:
            return ProcessMesh(sub, self._dim_names)
        return ProcessMesh(sub, self._dim_names[1:]) if sub.ndim else \
            ProcessMesh(sub.reshape(1), self._dim_names[-1:])

    def __eq__(self, other):
        return (isinstance(other, ProcessMesh)
                and self._mesh.shape == other._mesh.shape
                and (self._mesh == other._mesh).all()
                and self._dim_names == other._dim_names)

    def __hash__(self):
        return hash((self._mesh.tobytes(), self._mesh.shape,
                     tuple(self._dim_names)))

    def __repr__(self):
        return (f"ProcessMesh(shape={self.shape}, dim_names={self._dim_names},"
                f" process_ids={self.process_ids})")

    def describe(self) -> dict:
        """Observability view of the topology: shape/axes/size plus the
        process (host) span — what the "Mesh:" profiler section and the
        multichip bench report print."""
        import jax

        return {"shape": self.shape, "dim_names": self.dim_names,
                "size": self.size,
                "processes": jax.process_count(),
                "local_devices": len(jax.local_devices())}

    # -- the XLA side -------------------------------------------------------
    def to_jax_mesh(self):
        return _jax_mesh_cached(self._mesh.tobytes(), self._mesh.shape,
                                tuple(self._dim_names))


@functools.lru_cache(maxsize=64)
def _jax_mesh_cached(ids_bytes, shape, dim_names):
    import jax
    from jax.sharding import Mesh

    ids = np.frombuffer(ids_bytes, dtype=np.int64).reshape(shape)
    devices = jax.devices()
    if ids.size and int(ids.max()) >= len(devices):
        # never wrap round: ranks stacked on the first chips would run,
        # slowly and out of memory, instead of saying what is missing
        raise ValueError(
            f"mesh names process id {int(ids.max())} but JAX has "
            f"{len(devices)} device(s)")
    dev_arr = np.empty(shape, dtype=object)
    for idx in np.ndindex(*shape):
        dev_arr[idx] = devices[int(ids[idx])]
    return Mesh(dev_arr, dim_names)


def set_mesh(mesh: ProcessMesh):
    """Set the global default mesh (reference `dist.auto_parallel.set_mesh`)."""
    global _global_mesh
    _global_mesh = mesh


def get_mesh() -> Optional[ProcessMesh]:
    return _global_mesh


def default_mesh(ndev: Optional[int] = None) -> ProcessMesh:
    """1-D world mesh over all devices."""
    import jax

    n = ndev or jax.device_count()
    return ProcessMesh(np.arange(n), ["world"])
