"""Static compiled-cost model + perf/carbon regression gate.

`python -m repro.analysis.cost --smoke --check BENCH_cost_baseline.json`

Runtime benchmarks are too noisy to gate in CI, but the compiled artifact
is deterministic: the same source always lowers to the same HLO, and the
HLO's FLOPs / bytes-moved / live-buffer footprint are exact static
quantities. This module extends the PR-8 audit sweep (`analysis/audit.py`)
from *invariant* gating (residency/donation/retraces) to *cost* gating:
for every id × backend cell (plus the fused-train cells) it lowers the
donated step program and emits a per-cell cost record:

  flops_per_step / bytes_per_step : trip-count-aware HLO totals from
      `launch/hlo_analysis.py`, normalised by env steps per program;
  peak_live_bytes  : static liveness-scan peak of the entry frame;
  collective bytes : per-step inter-chip traffic (sharded cells);
  arithmetic intensity + roofline : where the cell sits against the
      `benchmarks/roofline.py` machine ceilings (compute- vs memory- vs
      collective-bound, and the static time bound per step);
  xla_cost_analysis / xla_memory_analysis : XLA's own numbers alongside
      ours, for cross-checking (informational, not gated);
  static_impact : the CaiRL Table II analogue derived from the roofline
      bound — joules and gCO₂ per million env steps, at compile time
      (`sustainability.impact.StaticImpact`).

The regression gate: `check(report, baseline)` diffs the gated metrics
(GATED_METRICS) against a committed `BENCH_cost_baseline.json` with
per-family relative thresholds (DEFAULT_THRESHOLDS) and returns
`(problems, notes)` — problems name the cell, metric, and delta, and make
the CLI exit nonzero; improvements beyond threshold and new cells are
notes suggesting a reviewed `--regen-baseline`. `make cost-check` runs
this inside `make test-fast`, so a PR that inflates a fused env's compiled
cost >threshold fails loudly with zero timing noise.

Smoke mode sweeps the dispatch-distinct backends only (vmap + pallas: the
async/sharded step programs wrap the same cores, and the full matrix is
already residency-audited by `analysis.audit`); full mode covers all four.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from repro.analysis.audit import (BACKENDS, EXPECTED_REFUSALS, TRAIN_BACKEND,
                                  _build_pool, _lower_step)
from repro.core.registry import registered, spec
from repro.launch.hlo_analysis import analyze_hlo, peak_live_bytes
from repro.sustainability.impact import ACCELERATOR_TDP_WATTS, StaticImpact

from benchmarks.roofline import V5E, peaks

#: the chip the static model prices a step on: one TPU v5e
TARGET_DEVICE_KIND = V5E
_PEAKS = peaks(TARGET_DEVICE_KIND)

#: backends swept in smoke mode (the two distinct step-kernel paths; async/
#: sharded wrap the same cores and stay in the full sweep + audit matrix)
SMOKE_BACKENDS = ("vmap", "pallas")

#: metrics the regression gate diffs against the baseline (all exact static
#: quantities from our own parsers — XLA's numbers are informational)
GATED_METRICS = ("flops_per_step", "bytes_per_step", "peak_live_bytes")

#: per-family relative regression thresholds. Arcade carries the pixel
#: rasteriser (layout-sensitive fusion decisions) and train programs fold
#: whole learners in — both get more headroom than the small cores.
DEFAULT_THRESHOLDS: Dict[str, float] = {
    "classic": 0.10, "grid": 0.10, "puzzle": 0.10, "flash": 0.10,
    "arcade": 0.15, "train": 0.15,
}
FALLBACK_THRESHOLD = 0.10

_FAMILIES = ("classic", "grid", "arcade", "puzzle", "flash")


def family_of(env_id: str, backend: str = "vmap") -> str:
    """Env family (threshold bucket) of a cell: the registry spec tag for
    pool cells, the fixed "train" family for fused-train cells."""
    if backend == TRAIN_BACKEND:
        return "train"
    tags = spec(env_id).tags
    for fam in _FAMILIES:
        if fam in tags:
            return fam
    return "other"


def threshold_for(family: str,
                  thresholds: Optional[Dict[str, float]] = None) -> float:
    return (thresholds or DEFAULT_THRESHOLDS).get(family, FALLBACK_THRESHOLD)


def _xla_cost_analysis(compiled) -> Dict[str, float]:
    """XLA's own cost numbers, normalised (newer jax returns a dict, older
    a one-element list) and trimmed to the cross-checkable keys."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # repro: allow[silent-except] informational cross-check only; absent on some platforms
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    keep = ("flops", "bytes accessed", "optimal_seconds")
    return {k: float(ca[k]) for k in keep
            if isinstance(ca.get(k), (int, float))}


def _xla_memory_analysis(compiled) -> Dict[str, float]:
    """XLA's buffer-assignment sizes (unavailable on CPU backends)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # repro: allow[silent-except] informational cross-check only; raises NotImplementedError on CPU
        return {}
    out = {}
    for k in ("temp_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if isinstance(v, (int, float)):
            out[k] = float(v)
    return out


def _roofline(flops_ps: float, bytes_ps: float,
              coll_ps: float) -> Dict[str, Any]:
    """Static roofline position of one env step against the per-chip
    ceilings: per-term time bounds, the binding term, and where the cell's
    arithmetic intensity sits relative to the machine balance point."""
    compute_s = flops_ps / _PEAKS["flops"]
    memory_s = bytes_ps / _PEAKS["hbm_bw"]
    collective_s = coll_ps / _PEAKS["ici_bw"]
    terms = (("compute", compute_s), ("memory", memory_s),
             ("collective", collective_s))
    dominant, bound_s = max(terms, key=lambda kv: kv[1])
    balance = _PEAKS["flops"] / _PEAKS["hbm_bw"]  # FLOP/byte where compute == memory time
    intensity = flops_ps / bytes_ps if bytes_ps else 0.0
    return {
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": collective_s,
        "bound_s": bound_s, "dominant": dominant,
        "balance_intensity": balance,
        "intensity_vs_balance": intensity / balance if balance else 0.0,
    }


def _cost_record(row: Dict[str, Any], lowered, steps_per_program: int
                 ) -> Dict[str, Any]:
    """Fill `row` with the static cost of a lowered step program whose one
    execution advances `steps_per_program` env steps."""
    compiled = lowered.compile()
    hlo = compiled.as_text()
    analysis = analyze_hlo(hlo)
    n = max(steps_per_program, 1)
    flops_ps = analysis.flops / n
    bytes_ps = analysis.bytes / n
    coll_ps = analysis.collective_bytes / n
    roofline = _roofline(flops_ps, bytes_ps, coll_ps)
    impact = StaticImpact(seconds_per_step=roofline["bound_s"],
                          watts=ACCELERATOR_TDP_WATTS)
    row.update(
        status="ok",
        env_steps_per_program=steps_per_program,
        flops=analysis.flops,
        bytes=analysis.bytes,
        collective_bytes=analysis.collective_bytes,
        peak_live_bytes=peak_live_bytes(hlo),
        flops_per_step=flops_ps,
        bytes_per_step=bytes_ps,
        collective_bytes_per_step=coll_ps,
        arithmetic_intensity=flops_ps / bytes_ps if bytes_ps else 0.0,
        roofline=roofline,
        static_impact=impact.report(),
        xla_cost_analysis=_xla_cost_analysis(compiled),
        xla_memory_analysis=_xla_memory_analysis(compiled),
    )
    return row


def cost_cell(env_id: str, backend: str, batch: int) -> Dict[str, Any]:
    """Cost one (id, backend) pool cell; refusals are recorded rows, same
    named-refusal protocol as the audit."""
    row: Dict[str, Any] = {"id": env_id, "backend": backend, "batch": batch,
                           "family": family_of(env_id, backend)}
    try:
        pool = _build_pool(env_id, backend, batch)
        lowered, _ = _lower_step(pool, backend)
    except Exception as e:  # repro: allow[silent-except] named-refusal protocol: class+message recorded, judged against EXPECTED_REFUSALS
        row.update(status="refused", refusal=type(e).__name__,
                   refusal_msg=str(e).splitlines()[0][:200])
        return row
    # one program execution steps every env in the batch once
    return _cost_record(row, lowered, batch)


def cost_train_cell(gid: str, chunk: int = 8) -> Dict[str, Any]:
    """Cost one fused-train program (a GOLDEN_TRAIN_IDS "<algo>/<env>" id).

    Env steps per program: each of the `chunk` scanned train steps advances
    `num_envs` envs once (DQN) or through a full rollout (PPO).
    """
    row: Dict[str, Any] = {"id": gid, "backend": TRAIN_BACKEND,
                           "chunk": chunk, "family": "train"}
    try:
        from repro.train.fused import golden_train_setup, lower_train_chunk

        algo, env_id, cfg, _ = golden_train_setup(gid)
        row["batch"] = cfg.num_envs
        lowered, _ = lower_train_chunk(algo, env_id, cfg, chunk=chunk)
        steps = chunk * cfg.num_envs * getattr(cfg, "rollout_len", 1)
    except Exception as e:  # repro: allow[silent-except] named-refusal protocol (see cost_cell)
        row.update(status="refused", refusal=type(e).__name__,
                   refusal_msg=str(e).splitlines()[0][:200])
        return row
    return _cost_record(row, lowered, steps)


def plan(ids: Optional[Sequence[str]] = None,
         backends: Sequence[str] = BACKENDS) -> List[Tuple[str, str]]:
    """The cost matrix: every registry id × every requested backend (the
    audit matrix restricted to `backends`)."""
    ids = list(ids) if ids else sorted(registered())
    return [(i, b) for i in ids for b in backends]


def run(ids: Optional[Sequence[str]] = None,
        backends: Optional[Sequence[str]] = None, batch: int = 4,
        smoke: bool = True, train: Optional[bool] = None,
        chunk: int = 8, progress=None) -> Dict[str, Any]:
    """Run the cost sweep; returns the report dict.

    `train=None` means auto: on for full-registry sweeps, off with an
    explicit `ids` subset (same convention as the audit)."""
    if backends is None:
        backends = SMOKE_BACKENDS if smoke else BACKENDS
    cells = plan(ids, backends)
    train = (ids is None) if train is None else train
    rows: List[Dict[str, Any]] = []
    for env_id, backend in cells:
        row = cost_cell(env_id, backend, batch)
        rows.append(row)
        if progress:
            progress(row)
    train_ids: Tuple[str, ...] = ()
    if train:
        from repro.train.fused import GOLDEN_TRAIN_IDS

        train_ids = GOLDEN_TRAIN_IDS
        for gid in train_ids:
            row = cost_train_cell(gid, chunk=chunk)
            rows.append(row)
            if progress:
                progress(row)
    hosted = [r for r in rows if r["status"] == "ok"]
    unexpected = [r for r in rows if r["status"] == "refused"
                  and r["refusal"] not in EXPECTED_REFUSALS]
    return {
        "meta": {
            "smoke": smoke,
            "batch": batch,
            "chunk": chunk,
            "jax": jax.__version__,
            "platform": jax.default_backend(),
            "backends": list(backends),
            "ids": sorted({c[0] for c in cells}),
            "train_cells": list(train_ids),
            "thresholds": dict(DEFAULT_THRESHOLDS),
            "gated_metrics": list(GATED_METRICS),
            "ceilings": {"device_kind": TARGET_DEVICE_KIND,
                         "peak_flops": _PEAKS["flops"],
                         "hbm_bw": _PEAKS["hbm_bw"],
                         "ici_bw": _PEAKS["ici_bw"],
                         "accelerator_watts": ACCELERATOR_TDP_WATTS},
        },
        "rows": rows,
        "summary": {
            "cells": len(rows),
            "hosted": len(hosted),
            "refused": len(rows) - len(hosted),
            "unexpected_refusals": [f"{r['id']}×{r['backend']}: "
                                    f"{r['refusal']}" for r in unexpected],
        },
    }


def _key(row: Dict[str, Any]) -> Tuple[str, str]:
    return (row["id"], row["backend"])


def check(report: Dict[str, Any], baseline: Dict[str, Any],
          thresholds: Optional[Dict[str, float]] = None
          ) -> Tuple[List[str], List[str]]:
    """Diff a fresh cost report against the committed baseline.

    Returns `(problems, notes)`. Problems (gate failures, nonzero exit):
      - a gated metric regressed beyond the cell family's threshold
        (named cell + metric + relative delta);
      - a baseline-hosted cell is missing from or refused by the report;
      - a cell's batch/steps-per-program changed (costs not comparable).
    Notes (printed, never failing): improvements beyond threshold and new
    cells — both suggest a reviewed `--regen-baseline`.
    """
    problems: List[str] = []
    notes: List[str] = []
    new_rows = {_key(r): r for r in report["rows"]}
    base_rows = {_key(r): r for r in baseline["rows"]}
    base_platform = baseline.get("meta", {}).get("platform")
    platform = report.get("meta", {}).get("platform")
    if base_platform and platform and base_platform != platform:
        notes.append(f"platform changed {base_platform} -> {platform}; "
                     "compiled costs may legitimately differ")
    for key, base in sorted(base_rows.items()):
        tag = f"{key[0]}×{key[1]}"
        new = new_rows.get(key)
        if new is None:
            problems.append(f"{tag}: cell missing from the new report "
                            "(id or backend dropped?)")
            continue
        if base["status"] == "refused":
            if new["status"] == "ok":
                notes.append(f"{tag}: newly hosted (was refused: "
                             f"{base['refusal']}) — regen the baseline to "
                             "start gating it")
            continue
        if new["status"] == "refused":
            problems.append(f"{tag}: was hosted in the baseline, now "
                            f"refused ({new['refusal']}: "
                            f"{new.get('refusal_msg', '')})")
            continue
        for dim in ("batch", "env_steps_per_program"):
            if base.get(dim) != new.get(dim):
                problems.append(f"{tag}: {dim} changed "
                                f"{base.get(dim)} -> {new.get(dim)}; "
                                "costs not comparable — regen the baseline")
                break
        else:
            fam = new.get("family") or base.get("family", "other")
            thr = threshold_for(fam, thresholds)
            for metric in GATED_METRICS:
                b, n = base.get(metric, 0.0), new.get(metric, 0.0)
                if not b:
                    continue
                rel = (n - b) / b
                if rel > thr:
                    problems.append(
                        f"{tag}: {metric} regressed {rel:+.1%} "
                        f"({b:.4g} -> {n:.4g}; {fam} threshold "
                        f"{thr:.0%})")
                elif rel < -thr:
                    notes.append(
                        f"{tag}: {metric} improved {rel:+.1%} "
                        f"({b:.4g} -> {n:.4g}) — regen the baseline to "
                        "lock it in")
    for key in sorted(set(new_rows) - set(base_rows)):
        notes.append(f"{key[0]}×{key[1]}: new cell not in the baseline — "
                     "regen to start gating it")
    return problems, notes


def summary_table(report: Dict[str, Any]) -> str:
    """Per-family cost summary (the `make analyze` console table)."""
    by_fam: Dict[str, List[Dict[str, Any]]] = {}
    for r in report["rows"]:
        if r["status"] == "ok":
            by_fam.setdefault(r.get("family", "other"), []).append(r)
    lines = [f"  {'family':<8} {'cells':>5} {'flops/step':>12} "
             f"{'bytes/step':>12} {'peak live B':>12} {'dominant':>10} "
             f"{'J/Mstep':>10}"]
    for fam in sorted(by_fam):
        rows = by_fam[fam]
        med = sorted(r["flops_per_step"] for r in rows)[len(rows) // 2]
        medb = sorted(r["bytes_per_step"] for r in rows)[len(rows) // 2]
        peak = max(r["peak_live_bytes"] for r in rows)
        doms = [r["roofline"]["dominant"] for r in rows]
        dom = max(set(doms), key=doms.count)
        joules = max(r["static_impact"]["joules_per_mstep"] for r in rows)
        lines.append(f"  {fam:<8} {len(rows):>5} {med:>12.4g} {medb:>12.4g} "
                     f"{peak:>12.4g} {dom:>10} {joules:>10.4g}")
    return "\n".join(lines)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.cost",
        description="static compiled-cost model + perf/carbon regression "
                    "gate (see docs/analysis.md)")
    ap.add_argument("--smoke", action="store_true",
                    help="small batch, vmap+pallas backends only (the "
                         "make-cost-check / test-fast mode)")
    ap.add_argument("--ids", default="",
                    help="comma-separated id subset (default: full registry)")
    ap.add_argument("--backends", default="",
                    help=f"comma-separated backend subset of {BACKENDS} "
                         "(default: vmap,pallas in smoke, all four full)")
    ap.add_argument("--batch", type=int, default=0,
                    help="envs per pool (default: 4 smoke, 16 full)")
    ap.add_argument("--train", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="cost the fused-train programs too (default: auto "
                         "— on for full-registry sweeps, off with --ids)")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write the cost report as JSON")
    ap.add_argument("--check", default="", metavar="BASELINE",
                    help="diff against a committed baseline; exit nonzero "
                         "on any above-threshold regression")
    ap.add_argument("--regen-baseline", default="", metavar="BASELINE",
                    help="write the report as the new committed baseline "
                         "(review the diff!)")
    ap.add_argument("--table", action="store_true",
                    help="print the per-family cost summary table")
    args = ap.parse_args(argv)
    ids = [i.strip() for i in args.ids.split(",") if i.strip()] or None
    backends: Optional[Tuple[str, ...]] = tuple(
        b.strip() for b in args.backends.split(",") if b.strip()) or None
    if backends and (unknown := set(backends) - set(BACKENDS)):
        ap.error(f"unknown backends {sorted(unknown)}; expected {BACKENDS}")
    batch = args.batch or (4 if args.smoke else 16)

    def progress(row):
        if row["status"] == "ok":
            rl = row["roofline"]
            detail = (f"{row['flops_per_step']:.4g} flop/step, "
                      f"{row['bytes_per_step']:.4g} B/step, "
                      f"{rl['dominant']}-bound")
        else:
            detail = f"refused: {row['refusal']}"
        print(f"  {row['id']:>18} × {row['backend']:<11} "
              f"{row['status']:<7} {detail}", flush=True)

    report = run(ids=ids, backends=backends, batch=batch, smoke=args.smoke,
                 train=args.train, progress=progress)
    for path in (args.json, args.regen_baseline):
        if path:
            with open(path, "w") as f:
                json.dump(report, f, indent=2, sort_keys=True)
            print(f"repro.analysis.cost: wrote {path}")
    if args.table:
        print(summary_table(report))
    s = report["summary"]
    print(f"repro.analysis.cost: {s['cells']} cells "
          f"({s['hosted']} hosted, {s['refused']} refused)")
    rc = 0
    for r in s["unexpected_refusals"]:
        print(f"  UNEXPECTED REFUSAL: {r}")
        rc = 1
    if args.check:
        try:
            with open(args.check) as f:
                baseline = json.load(f)
        except FileNotFoundError:
            print(f"  BASELINE MISSING: {args.check} — run "
                  f"--regen-baseline {args.check} and commit it")
            return 1
        problems, notes = check(report, baseline)
        for n in notes:
            print(f"  note: {n}")
        for p in problems:
            print(f"  COST REGRESSION: {p}")
        print(f"repro.analysis.cost: gate "
              f"{'FAILED' if problems else 'ok'} vs {args.check} "
              f"({len(problems)} problem(s), {len(notes)} note(s))")
        rc = 1 if problems else rc
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
