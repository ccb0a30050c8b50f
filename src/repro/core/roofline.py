"""Roofline analysis driven by the tool itself (deliverable (g); DESIGN.md
§3).  Consumes ``compiled.cost_analysis()`` + the hpcstruct-analogue HLO
parse and reports the three terms per (arch x shape x mesh):

    compute    = HLO_FLOPs / (chips x peak FLOP/s)
    memory     = HLO_bytes / (chips x HBM bandwidth)
    collective = collective wire bytes / (chips x link bandwidth)

cost_analysis on an SPMD-partitioned module reports *per-device* flops and
bytes, so dividing by per-chip peaks directly equals the prompt's
total/(chips x peak) form.  Collective bytes are NOT in cost_analysis: they
are summed from the partitioned HLO text over all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute operand sizes, with a
ring-model wire multiplier (structure.collective_bytes).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, Optional

from repro.core.peaks import Peaks
from repro.core.structure import HloModule, collective_bytes, parse_hlo


@dataclasses.dataclass
class RooflineReport:
    name: str
    mesh: str
    chips: int
    hlo_flops_per_dev: float
    hlo_bytes_per_dev: float
    coll_operand_bytes: float
    coll_wire_bytes: float
    t_compute: float
    t_memory: float
    t_collective: float
    model_flops_total: float
    bytes_per_dev: Dict[str, float]
    peak_flops: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """No-overlap upper bound estimate (sum) and its max lower bound
        are both useful; we report max (perfect overlap) as the step time
        and keep the individual terms visible."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs_total — remat/padding/dispatch waste."""
        total_hlo = self.hlo_flops_per_dev * self.chips
        return self.model_flops_total / total_hlo if total_hlo else 0.0

    @property
    def mfu(self) -> float:
        """Roofline-model MFU: useful model flops / (chips*peak*step_time)."""
        denom = self.chips * self.peak_flops * self.step_time
        return self.model_flops_total / denom if denom else 0.0

    @property
    def roofline_fraction(self) -> float:
        """How close the dominant term pins execution to its roof: the
        fraction of step time the dominant resource is busy doing useful
        work.  For compute-bound this equals MFU."""
        if self.dominant == "compute":
            return self.mfu
        return (self.t_compute / self.step_time) if self.step_time else 0.0

    def row(self) -> dict:
        return {
            "name": self.name, "mesh": self.mesh, "chips": self.chips,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "model_flops": self.model_flops_total,
            "hlo_flops_per_dev": self.hlo_flops_per_dev,
            "hlo_bytes_per_dev": self.hlo_bytes_per_dev,
            "coll_operand_bytes_per_dev": self.coll_operand_bytes,
            "coll_wire_bytes_per_dev": self.coll_wire_bytes,
            "useful_ratio": self.useful_ratio,
            "mfu_model": self.mfu,
            "step_time_s": self.step_time,
        }


def analyze(name: str, mesh_desc: str, chips: int, cost: Dict[str, float],
            peaks: Peaks, hlo_text: Optional[str] = None,
            module: Optional[HloModule] = None,
            model_flops_total: float = 0.0) -> RooflineReport:
    """The three roofline terms of one compiled step at ``peaks``' rates
    (the chip the step was compiled for)."""
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    if module is None:
        module = parse_hlo(hlo_text or "", name=name)
    # XLA cost analysis counts while bodies once; scale by the parsed
    # trip-count-aware ratio (structure.HloModule.cost_scale).
    fr, br = module.cost_scale()
    flops *= fr
    nbytes *= br
    coll = collective_bytes(module)
    return RooflineReport(
        name=name, mesh=mesh_desc, chips=chips,
        hlo_flops_per_dev=flops,
        hlo_bytes_per_dev=nbytes,
        coll_operand_bytes=coll["operand_bytes"],
        coll_wire_bytes=coll["wire_bytes"],
        t_compute=flops / peaks.flops,
        t_memory=nbytes / peaks.hbm_bw,
        t_collective=coll["wire_bytes"] / peaks.ici_bw,
        model_flops_total=model_flops_total,
        bytes_per_dev={k: v for k, v in coll.items()
                       if k.startswith("operand_bytes/")},
        peak_flops=peaks.flops,
    )


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS convention: 6*N*D for training (N = params, D = tokens;
    active params for MoE), 2*N*D for prefill, 2*N_active*B per decoded
    token."""
    n_active = cfg.n_active_params()
    if shape.kind == "train":
        return 6.0 * n_active * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.seq_len * shape.global_batch
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def markdown_table(rows) -> str:
    cols = ["name", "mesh", "chips", "t_compute_s", "t_memory_s",
            "t_collective_s", "dominant", "model_flops",
            "useful_ratio", "mfu_model", "step_time_s"]
    out = ["| " + " | ".join(cols) + " |",
           "|" + "|".join(["---"] * len(cols)) + "|"]
    for r in rows:
        vals = []
        for c in cols:
            v = r[c] if isinstance(r, dict) else getattr(r, c)
            vals.append(f"{v:.3e}" if isinstance(v, float) else str(v))
        out.append("| " + " | ".join(vals) + " |")
    return "\n".join(out)
