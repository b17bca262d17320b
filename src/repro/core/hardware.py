"""Hardware descriptions used by the cost models and the roofline analysis.

The TPU v5e entry is the production target (constants fixed by the
assignment: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI). The paper's
GPU testbeds are included so the benchmark harness can re-run ProTrain's own
planner search under the paper's conditions (Tables 2-4) and compare against
the paper's reported numbers.
"""
from __future__ import annotations

import dataclasses


# Shared capacity fractions (planner + serving; docs/cost_model.md §1).
# HBM_CAPACITY_FRACTION is the usable slice of a chip's HBM the planners
# budget against — the remainder absorbs XLA's allocator slack, collective
# scratch, and fragmentation. It is the single source of truth for both the
# training search (core/autotuner.search capacity default, launch/dryrun's
# feasibility flag) and the serving planner (core/serve_plan).
HBM_CAPACITY_FRACTION = 0.92
# SERVE_RESIDENT_HEADROOM is serving-specific: the fraction of the *budget*
# that weights + KV cache may fill while still keeping everything resident.
# The reserve covers what the serve memory estimate does not enumerate —
# decode workspace, logits, and growth between planning and admission
# (scheduler admits until pages run out). Above this line the planner starts
# trading residency for host pages / ZeRO-sharded weights.
SERVE_RESIDENT_HEADROOM = 0.75


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops: float  # bf16/fp16 FLOP/s per chip
    hbm_bytes: float  # device memory per chip
    hbm_bw: float  # B/s per chip
    ici_bw: float  # B/s per link, intra-pod interconnect (ICI / NVLink)
    host_bw: float  # B/s device<->host (PCIe / host DMA)
    dcn_bw: float  # B/s per chip across pods (data-center network)
    host_mem_bytes: float  # host DRAM available for offload, per host
    chips_per_host: int = 4
    # Achievable fractions (dialed in from experience; exposed for calibration)
    flops_efficiency: float = 0.55  # MFU ceiling for dense matmul pipelines
    mem_efficiency: float = 0.8
    coll_efficiency: float = 0.85
    host_flops: float = 2.0e12  # host-side update throughput (fused CPU Adam analogue)
    # Capacity fractions (see module constants above for semantics); fields so
    # a HardwareSpec can be re-calibrated per deployment without touching the
    # shared defaults.
    hbm_capacity_fraction: float = HBM_CAPACITY_FRACTION
    serve_resident_headroom: float = SERVE_RESIDENT_HEADROOM

    def matmul_time(self, flops: float) -> float:
        return flops / (self.peak_flops * self.flops_efficiency)

    def hbm_time(self, nbytes: float) -> float:
        return nbytes / (self.hbm_bw * self.mem_efficiency)

    def capacity_bytes(self) -> float:
        """Plannable HBM per chip — the Eq. 1 M_capacity both the training
        search and the serving planner constrain against."""
        return self.hbm_bytes * self.hbm_capacity_fraction


TPU_V5E = HardwareSpec(
    name="tpu-v5e",
    peak_flops=197e12,
    hbm_bytes=16e9,
    hbm_bw=819e9,
    ici_bw=50e9,
    host_bw=25e9,
    dcn_bw=12.5e9,
    host_mem_bytes=512e9,
)

# Paper testbeds (Section 5.1), for reproducing Tables 2-4 / Figs 3-6.
RTX_3090 = HardwareSpec(
    name="rtx-3090",
    peak_flops=71e12,  # fp16 w/ fp32 accumulate
    hbm_bytes=24e9,
    hbm_bw=936e9,
    ici_bw=15.8e9,  # no NVLink: collectives ride PCIe 3
    host_bw=15.8e9,  # PCIe 3 x16
    dcn_bw=12.5e9,  # 100 Gb IB (paper section 5.5)
    host_mem_bytes=384e9,
    chips_per_host=4,
    host_flops=0.6e12,  # 24-core Xeon Silver, fused CPU Adam
)

A100_80G = HardwareSpec(
    name="a100-80g",
    peak_flops=312e12,
    hbm_bytes=80e9,
    hbm_bw=2039e9,
    ici_bw=300e9,  # NVLink 3.0
    host_bw=31.5e9,  # PCIe 4 x16
    dcn_bw=12.5e9,
    host_mem_bytes=1e12,
    chips_per_host=4,
    host_flops=2.5e12,  # 112-core Platinum 8480+
)

# Local-host CPU calibration for the fidelity harness and example plan
# summaries (benchmarks/estimator_fidelity.py, examples/train_lm.py): one
# shared set of constants so the example's printed estimates and the CI
# drift gate's predictions come from the same oracle.
LOCAL_CPU_HW = HardwareSpec(
    name="cpu-host",
    peak_flops=5e10,
    hbm_bytes=32e9,
    hbm_bw=20e9,
    ici_bw=10e9,
    host_bw=10e9,
    dcn_bw=1e9,
    host_mem_bytes=32e9,
)

HARDWARE = {h.name: h for h in (TPU_V5E, RTX_3090, A100_80G)}

# Accelerators the planner can target, keyed by ``Device.device_kind`` as
# jax reports it. TPU_V5E's peaks: Google Cloud documentation, "TPU v5e".
DEVICE_KINDS = {"TPU v5 lite": TPU_V5E}


def host_memory_bytes(meminfo: str = "/proc/meminfo") -> float:
    """The host memory free for offload now, in bytes: ``MemAvailable`` of
    /proc/meminfo. Not ``MemTotal``: by the time a plan is made, the
    accelerator's runtime already holds part of it (13.9 GB on a one-chip
    TPU v5e host)."""
    with open(meminfo) as f:
        for line in f:
            if line.startswith("MemAvailable:"):
                return float(line.split()[1]) * 1024
    raise RuntimeError(f"no MemAvailable line in {meminfo}")


def hardware_for_device(device) -> HardwareSpec:
    """The spec of ``device``'s accelerator, with this host's free memory.

    An unknown ``device_kind`` is an error: planning a chip against another
    chip's constants would produce a plan that does not fit or wastes it.
    """
    spec = DEVICE_KINDS.get(device.device_kind)
    if spec is None:
        raise ValueError(f"no HardwareSpec for device kind {device.device_kind!r} "
                         f"(known: {sorted(DEVICE_KINDS)})")
    return dataclasses.replace(spec, host_mem_bytes=host_memory_bytes())


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh geometry + per-axis bandwidth class."""

    shape: tuple[int, ...]
    axes: tuple[str, ...]

    @property
    def n_chips(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    def axis_size(self, name: str) -> int:
        return self.shape[self.axes.index(name)] if name in self.axes else 1

    @property
    def zero_axes(self) -> tuple[str, ...]:
        return tuple(a for a in self.axes if a in ("pod", "data"))

    @property
    def zero_degree(self) -> int:
        n = 1
        for a in self.zero_axes:
            n *= self.axis_size(a)
        return n

    @property
    def tp_degree(self) -> int:
        return self.axis_size("model")

    def gather_bw(self, hw: HardwareSpec) -> float:
        """Effective per-chip bandwidth for a ZeRO all-gather.

        Ring all-gather over the slowest participating axis dominates; when
        the ``pod`` axis participates the DCN leg is the bottleneck.
        """
        if "pod" in self.axes and self.axis_size("pod") > 1:
            return hw.dcn_bw * hw.coll_efficiency
        return hw.ici_bw * hw.coll_efficiency


SINGLE_POD = MeshSpec((16, 16), ("data", "model"))
MULTI_POD = MeshSpec((2, 16, 16), ("pod", "data", "model"))
