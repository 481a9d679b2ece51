"""Chip/interconnect facts the cost model prices plans against.

One :class:`ChipTopology` per TPU generation: peak matmul throughput (the
same public figures ``utils.perf.PEAK_TFLOPS_PER_CHIP`` uses for MFU — one
source of truth via ``peak_tflops_key``), HBM capacity, and the ICI numbers
analytic collective costs are built from.  ``ici_bandwidth_bytes`` is the
usable per-chip bisection-ish figure for ring collectives (per direction,
per link, derated for protocol overhead), ``ici_latency_seconds`` the
per-hop software+wire latency that dominates small transfers.

A ``cpu`` entry exists so the planner is exercisable (and testable) off
hardware: the ratios are chosen to keep ranking behavior realistic (compute
slow, comms slower still) rather than to model any real host fabric.

``dcn_bandwidth_bytes`` prices the slow inter-slice fabric for worlds larger
than one slice; the planner currently treats the whole world as one ICI
domain and leaves multi-slice pricing as a documented blind spot
(docs/autotuning.md).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Optional

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class ChipTopology:
    """Static per-chip facts of one TPU generation."""

    name: str
    #: key into utils.perf.PEAK_TFLOPS_PER_CHIP (MFU's table — shared)
    peak_tflops_key: str
    hbm_bytes: int
    #: usable ring-collective bandwidth per chip, bytes/s (per direction)
    ici_bandwidth_bytes: float
    #: per-hop latency floor, seconds
    ici_latency_seconds: float
    #: inter-slice (DCN) bandwidth per chip, bytes/s
    dcn_bandwidth_bytes: float = 25.0e9 / 8
    #: matmul efficiency the compute roofline assumes (achievable MFU on
    #: large well-tiled matmuls, not the marketing peak)
    compute_efficiency: float = 0.55
    #: default fraction of collective wire time the XLA scheduler hides
    #: under concurrent compute on this generation (async collective fusion,
    #: per-layer gather-matmul pipelining) — the cost model's prior when no
    #: MEASURED calibration is supplied (``telemetry.trace`` writes the
    #: measured figure to ``trace_summary.json``; ``tools/plan.py
    #: --calibrate-from`` feeds it back in and overrides this)
    comms_overlap: float = 0.5

    @property
    def peak_flops(self) -> float:
        from neuronx_distributed_training_tpu.utils.perf import (
            PEAK_TFLOPS_PER_CHIP,
        )

        return PEAK_TFLOPS_PER_CHIP[self.peak_tflops_key] * 1e12


#: the topology table --apply/--topology select from.  ICI figures are the
#: public per-chip numbers derated to ~80% usable; HBM leaves the runtime's
#: own reservation alone (the planner applies its headroom separately).
TOPOLOGIES: dict[str, ChipTopology] = {
    "v5e": ChipTopology(
        name="v5e",
        peak_tflops_key="v5e",
        hbm_bytes=16 * 1024**3,
        # 2D torus, ~45 GB/s/dir/link; a ring collective drives both
        # directions of one axis -> ~90 GB/s effective per chip
        ici_bandwidth_bytes=90e9,
        ici_latency_seconds=1e-6,
        comms_overlap=0.5,
    ),
    "v5p": ChipTopology(
        name="v5p",
        peak_tflops_key="v5p",
        hbm_bytes=95 * 1024**3,
        # 3D torus, ~90 GB/s/dir/link, bidirectional ring
        ici_bandwidth_bytes=180e9,
        ici_latency_seconds=1e-6,
        # 3D torus: more ring axes available to schedule around, and the
        # latency-hiding scheduler has deeper HBM headroom for prefetch
        comms_overlap=0.55,
    ),
    "v6e": ChipTopology(
        name="v6e",
        peak_tflops_key="v6e",
        hbm_bytes=32 * 1024**3,
        ici_bandwidth_bytes=180e9,
        ici_latency_seconds=1e-6,
        comms_overlap=0.55,
    ),
    "v4": ChipTopology(
        name="v4",
        peak_tflops_key="v4",
        hbm_bytes=32 * 1024**3,
        # 3D torus, ~45 GB/s/dir/link, bidirectional ring
        ici_bandwidth_bytes=90e9,
        ici_latency_seconds=1e-6,
        comms_overlap=0.45,
    ),
    # off-hardware planning/test fallback: ratios realistic, magnitudes not
    "cpu": ChipTopology(
        name="cpu",
        peak_tflops_key="cpu",
        hbm_bytes=8 * 1024**3,
        ici_bandwidth_bytes=2e9,
        ici_latency_seconds=20e-6,
        compute_efficiency=0.5,
    ),
}


def resolve_topology(name: Optional[str] = None,
                     device: Optional[Any] = None) -> ChipTopology:
    """Topology by explicit name, else from a jax device's ``device_kind``.
    Unknown names and unknown kinds raise with the valid set (the CLI's
    ``--topology`` funnels through here): a default table would approve
    plans that OOM on the real chip.  A CPU device resolves to the ``cpu``
    row, the planner's off-hardware table."""
    if name:
        key = str(name).lower()
        if key not in TOPOLOGIES:
            raise ValueError(
                f"unknown topology {name!r}; known: "
                f"{'/'.join(sorted(TOPOLOGIES))}"
            )
        return TOPOLOGIES[key]
    if device is None:
        raise ValueError(
            "resolve_topology needs a topology name or a device; known "
            f"names: {'/'.join(sorted(TOPOLOGIES))}"
        )
    if device.platform == "cpu":
        return TOPOLOGIES["cpu"]
    kind = device.device_kind.lower()
    for key in ("v6e", "v6", "v5p", "v5e", "v4"):
        if key in kind or (key == "v5e" and "lite" in kind):
            return TOPOLOGIES["v6e" if key.startswith("v6") else key]
    raise ValueError(
        f"unrecognized device_kind {device.device_kind!r} "
        f"({device.platform}): pass an explicit topology (known: "
        f"{'/'.join(sorted(TOPOLOGIES))})"
    )
