"""Device mesh construction.

Replaces the reference's MachineView/MachineResource machinery (reference
include/flexflow/machine_view.h:18,102 and src/runtime/machine_view.cc): where
the reference describes an n-D strided GPU grid per operator and a custom
Legion mapper routes tasks to it, on TPU we build one ``jax.sharding.Mesh``
whose named axes carry the parallelism degrees, and GSPMD does the routing.

Axis names:
  data   — data parallelism (batch dim)
  model  — tensor parallelism (hidden/head dims)
  pipe   — pipeline stages (serving layer placement)
  seq    — sequence/context parallelism (ring attention; new vs reference)
  expert — expert parallelism
Only axes with degree > 1 are materialized in the mesh.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh


AXIS_ORDER = ("pipe", "data", "expert", "seq", "model")


@dataclasses.dataclass
class MachineResource:
    """Cluster inventory (reference machine_view.h:102 MachineResource)."""

    num_nodes: int
    num_devices_per_node: int

    @property
    def num_devices(self) -> int:
        return self.num_nodes * self.num_devices_per_node


def make_mesh(config, devices: Optional[Sequence] = None) -> Mesh:
    """Build the mesh implied by FFConfig parallelism degrees.

    Devices are laid out so that the innermost (fastest-varying) mesh axis is
    "model" — tensor-parallel collectives ride neighboring ICI links; "pipe"
    and "data" are outermost, matching the reference's placement of TP within
    a node and DP/PP across nodes (reference inference_manager.cc:95-132).
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if config.num_devices is not None:
        # the same device count the search prices: a model asked to use
        # fewer chips than the host has (one serving replica on a four-chip
        # host) must not absorb the rest into data parallelism below
        if config.num_devices > len(devices):
            raise ValueError(f"num_devices={config.num_devices} but only "
                             f"{len(devices)} devices are visible")
        devices = devices[:config.num_devices]
    n = len(devices)

    if config.mesh_shape is not None:
        shape = tuple(config.mesh_shape)
        names = tuple(config.mesh_axis_names)[: len(shape)]
        need = int(np.prod(shape))
        if need > n:
            raise ValueError(f"mesh_shape {shape} needs {need} devices, have {n}")
        return Mesh(np.array(devices[:need]).reshape(shape), names)

    degrees = {
        "pipe": config.pipeline_parallelism_degree,
        "data": config.data_parallelism_degree,
        "expert": config.expert_parallelism_degree,
        "seq": config.sequence_parallelism_degree,
        "model": config.tensor_parallelism_degree,
    }
    explicit = int(np.prod([d for d in degrees.values()]))
    if explicit > n:
        raise ValueError(
            f"parallelism degrees {degrees} need {explicit} devices, have {n}")
    # Absorb leftover devices into data parallelism (the reference's default
    # is data-parallel over all workers, model.h:303).
    if n % explicit != 0:
        devices = devices[: (n // explicit) * explicit]
        n = len(devices)
    degrees["data"] *= n // explicit

    axis_names = [a for a in AXIS_ORDER if degrees[a] > 1]
    shape = [degrees[a] for a in axis_names]
    if not axis_names:
        axis_names = ["data"]
        shape = [1]
        devices = devices[:1]
    mesh_devices = np.array(devices[: int(np.prod(shape))]).reshape(shape)
    return Mesh(mesh_devices, axis_names)


def single_device_mesh(device=None) -> Mesh:
    if device is None:
        device = jax.devices()[0]
    return Mesh(np.array([device]), ("data",))
