"""Per-chip peaks by the `device_kind` JAX reports, with their source.

A kind that is not in the table raises: no device is priced at another's
peaks.
"""
from __future__ import annotations

from typing import Dict

#: `jax.Device.device_kind` of one TPU v5e chip
V5E = "TPU v5 lite"

#: "flops" (bf16 MXU FLOP/s), "hbm_bw" (HBM bytes/s), "ici_bw" (bytes/s per
#: chip-to-chip link), "hbm_bytes" (HBM capacity)
PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over 4 links.
    V5E: {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9,
          "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The `PEAKS` row of `device_kind`; an unknown kind raises KeyError."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)}")
    return PEAKS[device_kind]
