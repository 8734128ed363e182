"""Three-term roofline from a compiled dry-run artifact (TPU v5e targets).

  compute    = HLO_FLOPs_per_chip / peak_FLOP/s
  memory     = HLO_bytes_per_chip / HBM_bw
  collective = collective_bytes_per_chip / (links * link_bw)

The parser reports per-partition numbers (the module is SPMD-partitioned),
so no further division by chip count is needed. MODEL_FLOPS uses the
analytic 6*N*D (dense) / 6*N_active*D (MoE), 2*N*D for decode.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Optional

from repro.peaks import PEAKS
from repro.roofline import hlo_parse

PEAK_FLOPS = PEAKS["TPU v5 lite"].flops  # the dry-run's target chip
HBM_BW = PEAKS["TPU v5 lite"].hbm_bw
ICI_LINK_BW = 50e9  # B/s / link (assignment constant)
ICI_LINKS = 1  # conservative: per-chip collective bandwidth = 1 link


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    flops: float  # per chip per step
    hbm_bytes: float
    coll_bytes: float
    coll_by_kind: dict
    model_flops_per_chip: float
    xla_reported_flops: Optional[float] = None
    xla_reported_bytes: Optional[float] = None
    argument_bytes: Optional[float] = None
    output_bytes: Optional[float] = None
    temp_bytes: Optional[float] = None

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / (ICI_LINKS * ICI_LINK_BW)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time_lb(self) -> float:
        """Lower bound step time = max of the three terms (perfect overlap)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — remat/dispatch/redundancy waste."""
        return self.model_flops_per_chip / max(self.flops, 1.0)

    @property
    def roofline_fraction(self) -> float:
        """Achievable fraction of compute roofline: time the chip would spend
        doing useful model FLOPs vs the bound step time."""
        t_useful = self.model_flops_per_chip / PEAK_FLOPS
        return t_useful / max(self.step_time_lb, 1e-30)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(t_compute=self.t_compute, t_memory=self.t_memory,
                 t_collective=self.t_collective, bottleneck=self.bottleneck,
                 step_time_lb=self.step_time_lb,
                 useful_flops_ratio=self.useful_flops_ratio,
                 roofline_fraction=self.roofline_fraction)
        return d


def analyze(compiled, *, arch: str, shape: str, mesh_name: str, n_chips: int,
            model_flops_total: float) -> Roofline:
    costs = hlo_parse.module_costs(compiled.as_text())
    ma = None
    ca = compiled.cost_analysis() or {}
    try:
        ma = compiled.memory_analysis()
    except Exception:
        pass
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name,
        flops=costs.flops,
        hbm_bytes=costs.hbm_bytes,
        coll_bytes=costs.coll_bytes,
        coll_by_kind=costs.coll_by_kind,
        model_flops_per_chip=model_flops_total / n_chips,
        xla_reported_flops=ca.get("flops"),
        xla_reported_bytes=ca.get("bytes accessed"),
        argument_bytes=getattr(ma, "argument_size_in_bytes", None),
        output_bytes=getattr(ma, "output_size_in_bytes", None),
        temp_bytes=getattr(ma, "temp_size_in_bytes", None),
    )


def save(r: Roofline, path: str) -> None:
    with open(path, "w") as f:
        json.dump(r.to_dict(), f, indent=1, default=float)
