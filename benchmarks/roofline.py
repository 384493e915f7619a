"""Roofline derivation from the dry-run JSONs (EXPERIMENTS.md §Roofline).

Hardware model: per-chip peaks keyed by the `device_kind` JAX reports
(`PEAKS`, each row with its source). A kind that is not in the table raises;
no device is priced at another's peaks.

Terms (seconds; cost_analysis / HLO collective bytes are PER-DEVICE, so
dividing by per-chip rates directly gives the per-step time bound — equal to
the global-quantity formulas in the task statement divided through by chips):
  compute    = flops_per_device / peak flops
  memory     = bytes_per_device / HBM bandwidth
  collective = collective_bytes_per_device / ICI link bandwidth
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

#: `jax.Device.device_kind` of one TPU v5e chip
V5E = "TPU v5 lite"

#: per-chip peaks by device kind: "flops" (bf16 FLOP/s), "hbm_bw" (B/s),
#: "ici_bw" (B/s per chip-to-chip link)
PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over 4 links.
    V5E: {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The `PEAKS` row of `device_kind`; an unknown kind raises KeyError."""
    if device_kind not in PEAKS:
        raise KeyError(f"no roofline peaks for device_kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def load_cells(directory: str = "results/dryrun") -> List[Dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def model_flops(cell: Dict) -> float:
    """6·N·D for training, 2·N_active·D for one forward token-batch."""
    n_act = cell.get("active_params", cell.get("params", 0))
    if cell["kind"] == "train":
        tokens = cell["seq_len"] * cell["global_batch"]
        return 6.0 * n_act * tokens
    if cell["kind"] == "prefill":
        tokens = cell["seq_len"] * cell["global_batch"]
        return 2.0 * n_act * tokens
    # decode: one token per sequence
    return 2.0 * n_act * cell["global_batch"]


def roofline_terms(cell: Dict) -> Optional[Dict]:
    if cell.get("status") != "ok":
        return None
    pk = peaks(V5E)  # the dry-run cells are priced as v5e pods
    chips = cell["chips"]
    compute_s = cell["flops_per_device"] / pk["flops"]
    memory_s = cell["bytes_per_device"] / pk["hbm_bw"]
    coll_s = cell["collective_bytes_per_device"]["total"] / pk["ici_bw"]
    dominant = max(
        ("compute", compute_s), ("memory", memory_s), ("collective", coll_s),
        key=lambda kv: kv[1],
    )[0]
    mf = model_flops(cell)
    hlo_global = cell["flops_per_device"] * chips
    return {
        "arch": cell["arch"], "shape": cell["shape"], "mesh": cell["mesh"],
        "compute_s": compute_s, "memory_s": memory_s, "collective_s": coll_s,
        "dominant": dominant,
        "model_flops": mf,
        "useful_ratio": (mf / hlo_global) if hlo_global else 0.0,
        "bound_s": max(compute_s, memory_s, coll_s),
        # fraction of roofline-limited time that is useful model compute
        "roofline_fraction": (mf / chips / pk["flops"]) / max(compute_s, memory_s, coll_s)
        if max(compute_s, memory_s, coll_s) > 0 else 0.0,
        "temp_gib": cell.get("memory_analysis", {}).get("temp_size_in_bytes", 0) / 2**30,
    }


def table(directory: str = "results/dryrun", mesh: str = "pod16x16") -> List[Dict]:
    rows = []
    for cell in load_cells(directory):
        if cell.get("mesh") != mesh:
            continue
        t = roofline_terms(cell)
        if t:
            rows.append(t)
    return rows


def render_markdown(rows: List[Dict]) -> str:
    hdr = ("| arch | shape | compute (s) | memory (s) | collective (s) | dominant "
           "| MODEL_FLOPS | useful | roofline frac | temp GiB |\n"
           "|---|---|---|---|---|---|---|---|---|---|\n")
    body = ""
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        body += (f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | {r['memory_s']:.3e} "
                 f"| {r['collective_s']:.3e} | {r['dominant']} | {r['model_flops']:.2e} "
                 f"| {r['useful_ratio']:.2f} | {r['roofline_fraction']:.3f} "
                 f"| {r['temp_gib']:.1f} |\n")
    return hdr + body


def main() -> None:
    for mesh in ("pod16x16", "pod2x16x16"):
        rows = table(mesh=mesh)
        if not rows:
            continue
        print(f"\n== roofline ({mesh}) ==")
        print(render_markdown(rows))


if __name__ == "__main__":
    main()
