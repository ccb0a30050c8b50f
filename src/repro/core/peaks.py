"""Per-chip peak rates, keyed by ``device_kind`` as JAX reports it.

Every roofline term, PC-sample weight and counter busy-time model in
this package divides by these rates, so they come from one table.  A
chip whose kind the table lacks is an error, never a default: a
profile of an unknown chip weighted by another chip's rates would look
plausible and be wrong.

Source of the v5e row: Google Cloud documentation, "TPU v5e" — per
chip 197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 1,600 Gbit/s of
inter-chip interconnect over 4 links (50 GB/s per link).  ``vmem_bw``
is not published; it is the kernel-interior model's estimate of the
VMEM <-> vector-unit bandwidth (repro.core.kstruct).
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Peaks:
    kind: str           # jax Device.device_kind
    flops: float        # bf16 FLOP/s per chip
    hbm_bw: float       # bytes/s per chip
    ici_bw: float       # bytes/s per link
    vmem_bw: float      # bytes/s VMEM <-> vector units (model estimate)


PEAKS: Dict[str, Peaks] = {p.kind: p for p in (
    Peaks("TPU v5 lite", flops=197e12, hbm_bw=819e9, ici_bw=50e9,
          vmem_bw=2.2e13),
)}

# The CPU backend runs the tests and the interpret-mode Pallas kernels
# as a stand-in for one chip: the kernels are tiled for it, and its
# modeled rates are that chip's row.
CPU_STANDS_IN_FOR = "TPU v5 lite"


def peaks_for(kind: str) -> Peaks:
    """The row for ``kind``; raises KeyError for a kind the table lacks."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {kind!r} "
                       f"(known: {sorted(PEAKS)})") from None


def device_peaks() -> Peaks:
    """The rates of the device this process computes on: a TPU's own
    row, or on the CPU backend the chip it stands in for."""
    import jax
    dev = jax.devices()[0]
    return peaks_for(CPU_STANDS_IN_FOR if dev.platform == "cpu"
                     else dev.device_kind)
