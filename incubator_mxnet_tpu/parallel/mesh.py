"""Device meshes.

The reference discovers GPU topology and builds reduction trees
(``src/kvstore/gpu_topology.h``); on TPU the torus topology is already known
to XLA, so "topology awareness" is just choosing mesh axis sizes — XLA maps
mesh axes onto ICI rings itself.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"
PIPE_AXIS = "pipe"


# the names every parallel module (pipeline, ring attention, zero) calls
shard_map_compat = jax.shard_map


def axis_size_compat(axis_name: str) -> int:
    """The size of a mesh axis from inside shard_map."""
    return jax.lax.axis_size(axis_name)


class _MeshState(threading.local):
    def __init__(self):
        self.stack = []


_state = _MeshState()


def make_mesh(axes: Optional[Dict[str, int]] = None, *,
              devices=None) -> Mesh:
    """Create a Mesh over the visible devices.

    ``axes`` maps axis name -> size; a size of -1 absorbs the remaining
    devices. Default: all devices on the ``data`` axis (pure DP).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    axes = dict(axes or {DATA_AXIS: -1})
    known = 1
    wild = None
    for k, v in axes.items():
        if v == -1:
            if wild is not None:
                raise ValueError("only one axis may be -1")
            wild = k
        else:
            known *= v
    if wild is not None:
        if n % known != 0:
            raise ValueError(f"{n} devices not divisible by {known}")
        axes[wild] = n // known
    total = int(np.prod(list(axes.values())))
    if total != n:
        raise ValueError(f"mesh {axes} needs {total} devices, have {n}")
    arr = np.array(devices).reshape(tuple(axes.values()))
    return Mesh(arr, tuple(axes.keys()))


def current_mesh() -> Optional[Mesh]:
    return _state.stack[-1] if _state.stack else None


class mesh_scope:
    """``with mesh_scope(mesh):`` — set the ambient mesh."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __enter__(self):
        _state.stack.append(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        _state.stack.pop()
