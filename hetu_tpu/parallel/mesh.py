"""Device mesh construction — the spine of every parallelism strategy.

Replaces the reference's NCCL communicator bootstrap
(communicator/mpi_nccl_comm.py:62-250: MPI init, hashed group ids,
sub-communicators per DeviceGroup).  On TPU a single `jax.sharding.Mesh`
with named axes ('dp','tp','pp','ep','cp' over ICI; 'dcn' over multi-slice)
subsumes all communicator groups: collectives are axis-name-addressed and
XLA routes them over the right interconnect.

Multi-host bring-up is `jax.distributed.initialize()` (replacing
`wrapped_mpi_nccl_init`, executor.py:60-71).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
import jax
from jax.sharding import Mesh


# canonical axis order: dcn-ish outermost, fastest-varying innermost so that
# tp/cp (highest-bandwidth-need) axes map to adjacent ICI neighbors
AXIS_ORDER = ("dcn", "pp", "dp", "ep", "cp", "tp")


@dataclass
class MeshAxes:
    dp: int = 1
    tp: int = 1
    pp: int = 1
    ep: int = 1
    cp: int = 1
    dcn: int = 1

    def total(self):
        return self.dp * self.tp * self.pp * self.ep * self.cp * self.dcn


def local_device_count():
    return jax.local_device_count()


def make_mesh(axes=None, devices=None, **kwargs):
    """Build a Mesh from axis sizes.  ``axes`` may be a MeshAxes, a dict
    {'dp': 4, 'tp': 2}, or kwargs.  Size -1 on one axis means "all remaining
    devices"."""
    if axes is None:
        axes = kwargs
    if isinstance(axes, MeshAxes):
        axes = {k: getattr(axes, k) for k in
                ("dcn", "pp", "dp", "ep", "cp", "tp")}
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    sizes = {k: int(v) for k, v in axes.items()}
    # resolve a single -1
    known = math.prod(v for v in sizes.values() if v > 0)
    for k, v in sizes.items():
        if v == -1:
            sizes[k] = n // known
    names = [a for a in AXIS_ORDER if sizes.get(a, 1) > 1]
    # axes outside the canonical set (e.g. 'ici' for hierarchical A2A)
    # append innermost in caller order
    names += [a for a in sizes if a not in AXIS_ORDER and sizes[a] > 1]
    if not names:
        names = [next(iter(sizes))] if sizes else ["dp"]
    dims = [sizes.get(a, 1) for a in names]
    total = math.prod(dims)
    assert total <= n, f"mesh {dict(zip(names, dims))} needs {total} devices, have {n}"
    arr = np.array(devices[:total]).reshape(dims)
    return Mesh(arr, tuple(names))


def default_mesh(dp=None):
    """All local devices on one 'dp' axis (the AllReduce-DP default,
    reference DataParallel strategy simple.py:6-39)."""
    n = dp or jax.device_count()
    return make_mesh({"dp": n})


def batch_axis(mesh, batch, exclude=None):
    """The mesh axis that carries the batch dim, or None: 'dp' when the
    mesh has one — on a pure expert-parallel mesh tokens are
    data-parallel over 'ep' (reference MoE: DP and EP share devices) —
    and only if ``batch`` divides it (B=1 inference on a training mesh
    stays replicated).  ``exclude``: an axis the caller uses otherwise.
    Feeds, the flash op and ring attention all ask here, so a batch
    enters every ``shard_map`` the way the executor laid it out —
    entering one replicated forces GSPMD to unshard and reshard around
    the call, while slicing a body with no cross-batch communication
    per data device is free."""
    axis = "dp" if "dp" in mesh.axis_names else "ep"
    if axis in mesh.axis_names and axis != exclude \
            and batch % mesh.shape[axis] == 0:
        return axis
    return None
