"""TPU machine model: the cost-model's view of the hardware.

Role-equivalent of the reference's SimpleMachineModel/EnhancedMachineModel
(reference src/runtime/machine_model.cc, include/flexflow/simulator.h:213-560),
which models GPU nodes, NVLink/PCIe/NIC bandwidths and routes comm paths.
On TPU the topology is regular — chips in a 2-D/3-D ICI torus within a slice,
DCN between slices — so the model reduces to a chip spec (MXU flops, HBM
bytes/s and capacity, per-link ICI bytes/s, link count) plus slice geometry.

Collective costs use the standard ring/torus lower bounds (the scaling-book
recipe): for N participants moving B bytes over bidirectional ICI with
aggregate bandwidth W per chip,
  all-gather / reduce-scatter:  B * (N-1)/N / W
  all-reduce:                   2 * B * (N-1)/N / W   (RS + AG)
  all-to-all:                   B * (N-1)/N / W  (torus routing approximation)
  ppermute (ring shift):        B / W_link  (one hop, one link)
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-chip peak numbers (public spec-sheet values)."""

    name: str
    bf16_flops: float           # peak MXU flop/s (bf16)
    hbm_bandwidth: float        # bytes/s
    hbm_capacity: float         # bytes
    ici_bandwidth: float        # aggregate bytes/s per chip over all ICI links
    ici_link_bandwidth: float   # bytes/s of one ICI link (one torus direction)
    dcn_bandwidth: float        # bytes/s per chip across slices
    # fraction of peak the roofline assumes achievable (MXU util on big gemms)
    flops_efficiency: float = 0.55
    mem_efficiency: float = 0.8
    # fixed per-op cost (HLO dispatch + fusion-boundary + pipeline-fill):
    # the sublinear-scaling term that makes over-sharding SMALL ops lose —
    # and branch-parallel (nonsequence-split) placement win by running
    # fewer, bigger per-device ops concurrently. The reference captures
    # this by MEASURING per-op costs (Op::measure_operator_cost); a pure
    # roofline is scale-linear and would never see it.
    op_overhead: float = 2e-6


TPU_CHIPS: Dict[str, ChipSpec] = {
    # Public spec-sheet numbers.
    "v5e": ChipSpec("v5e", bf16_flops=197e12, hbm_bandwidth=819e9,
                    hbm_capacity=16e9, ici_bandwidth=4 * 186e9 / 2,
                    ici_link_bandwidth=186e9 / 2, dcn_bandwidth=25e9),
    "v5p": ChipSpec("v5p", bf16_flops=459e12, hbm_bandwidth=2765e9,
                    hbm_capacity=95e9, ici_bandwidth=6 * 200e9 / 2,
                    ici_link_bandwidth=200e9 / 2, dcn_bandwidth=50e9),
    "v4": ChipSpec("v4", bf16_flops=275e12, hbm_bandwidth=1228e9,
                   hbm_capacity=32e9, ici_bandwidth=6 * 100e9 / 2,
                   ici_link_bandwidth=100e9 / 2, dcn_bandwidth=25e9),
    # Virtual-CPU chip for tests: tiny numbers so costs are nonzero and
    # ratios still favor parallelism the way real chips do.
    "cpu-sim": ChipSpec("cpu-sim", bf16_flops=1e11, hbm_bandwidth=2e10,
                        hbm_capacity=8e9, ici_bandwidth=5e9,
                        ici_link_bandwidth=2.5e9, dcn_bandwidth=1e9),
}


# jax ``device_kind`` strings -> TPU_CHIPS key (v5e reports "TPU v5 lite").
_DEVICE_KIND_CHIPS = {
    "tpu v5 lite": "v5e", "tpu v5e": "v5e",
    "tpu v5": "v5p", "tpu v5p": "v5p",
    "tpu v4": "v4",
}


def chip_for_device(device=None) -> str:
    """The TPU_CHIPS key for a JAX device (default ``jax.devices()[0]``).

    The one place a device is identified for peak numbers: the search's
    cost model (``FFConfig.tpu_chip=None``) and bench_train's MFU
    resolve through it. CPU maps to ``cpu-sim``;
    a device that is not in the table raises rather than borrow another
    chip's peaks."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return "cpu-sim"
    kind = str(device.device_kind)
    chip = _DEVICE_KIND_CHIPS.get(kind.strip().lower())
    if device.platform != "tpu" or chip is None:
        raise ValueError(
            f"no chip spec for platform={device.platform!r} "
            f"device_kind={kind!r}; add its public peak numbers to "
            "TPU_CHIPS/_DEVICE_KIND_CHIPS in search/machine_model.py")
    return chip


@dataclasses.dataclass
class MachineModel:
    """Slice geometry + chip spec → collective/time/memory primitives.

    ``dcn_model`` (optional, a network.NetworkedMachineModel over the
    SLICES) replaces the flat ``chip.dcn_bandwidth`` for cross-slice
    collectives with the routed inter-slice ring's bottleneck link — the
    reference's NetworkedMachineModel exists exactly to let topology
    change search outcomes (machine_model.cc / network.cc), and this is
    its TPU multi-slice counterpart: a skinny DCN fabric makes the search
    keep allreduce-heavy axes inside a slice."""

    chip: ChipSpec
    num_devices: int
    devices_per_slice: Optional[int] = None   # None → single slice
    dcn_model: Optional[object] = None        # network.NetworkedMachineModel

    @classmethod
    def from_name(cls, chip_name: Optional[str], num_devices: int,
                  devices_per_slice: Optional[int] = None,
                  dcn_model=None) -> "MachineModel":
        """``chip_name=None`` identifies the running device."""
        return cls(TPU_CHIPS[chip_name or chip_for_device()], num_devices,
                   devices_per_slice, dcn_model)

    @property
    def num_slices(self) -> int:
        per = self.devices_per_slice or self.num_devices
        return max(1, -(-self.num_devices // per))

    def _dcn_ring_bw(self) -> float:
        """Per-chip effective bandwidth of a cross-slice ring collective:
        the slowest routed slice-to-slice path's bottleneck link
        (network.NetworkedMachineModel.ring_bottleneck_bandwidth; a
        disconnected fabric returns ~0, i.e. effectively infinite cost)."""
        bw = self.dcn_model.ring_bottleneck_bandwidth(
            list(range(self.num_slices)))
        return max(bw, 1e-9)         # keep downstream divisions finite

    # ---- compute / memory primitives -------------------------------------
    def gemm_time(self, flops: float) -> float:
        return flops / (self.chip.bf16_flops * self.chip.flops_efficiency)

    def mem_time(self, bytes_moved: float) -> float:
        return bytes_moved / (self.chip.hbm_bandwidth * self.chip.mem_efficiency)

    def op_time(self, flops: float, bytes_moved: float) -> float:
        """Roofline: an op is MXU-bound or HBM-bound, XLA overlaps the rest;
        plus the fixed per-op overhead (see ChipSpec.op_overhead)."""
        return (max(self.gemm_time(flops), self.mem_time(bytes_moved))
                + self.chip.op_overhead)

    # ---- collective primitives ------------------------------------------
    def _group_bw(self, group_size: int) -> float:
        """Bandwidth available to a collective over a mesh-axis group. Groups
        that fit a slice ride ICI; larger groups are DCN-bound (through the
        routed slice topology's bottleneck when one is modeled)."""
        per_slice = self.devices_per_slice or self.num_devices
        if group_size <= per_slice:
            return self.chip.ici_bandwidth
        if self.dcn_model is not None:
            return self._dcn_ring_bw()
        return self.chip.dcn_bandwidth

    def all_reduce_time(self, bytes_per_chip: float, group: int) -> float:
        if group <= 1:
            return 0.0
        return 2.0 * bytes_per_chip * (group - 1) / group / self._group_bw(group)

    def all_gather_time(self, bytes_per_chip: float, group: int) -> float:
        if group <= 1:
            return 0.0
        return bytes_per_chip * (group - 1) / group / self._group_bw(group)

    def reduce_scatter_time(self, bytes_per_chip: float, group: int) -> float:
        return self.all_gather_time(bytes_per_chip, group)

    def all_to_all_time(self, bytes_per_chip: float, group: int) -> float:
        if group <= 1:
            return 0.0
        return bytes_per_chip * (group - 1) / group / self._group_bw(group)

    def ppermute_time(self, bytes_per_chip: float) -> float:
        return bytes_per_chip / self.chip.ici_link_bandwidth

    def memory_per_device(self) -> float:
        return self.chip.hbm_capacity
