"""Table II reproduction: energy & CO₂, CaiRL vs interpreted Gym.

Paper methodology (§V-C): run DQN + env, track energy/emissions with the
impact tracker, isolate the environment's share by subtracting learner-only
cost. Console variant (1e6 steps in the paper) and graphical variant
(1e4 steps), both scaled to this host's budget.
"""
from __future__ import annotations

import dataclasses

import jax

from repro.configs.cairl_dqn import PAPER_TABLE_I
from repro.core import PythonRunner, make, rollout_random
from repro.envs.baseline_python import BASELINES
from repro.sustainability.impact import ImpactTracker


def _measure(fn):
    with ImpactTracker() as t:
        fn()
    return t.impact


def run(console_steps: int = 160_000, render_steps: int = 1600):
    env = make("CartPole-v1")
    batch = 64
    # warm-up compiles excluded from the measurement, as the paper excludes
    # C++ compile time (it is paid once per binary, not per experiment).
    # Must use the SAME static shapes as the measured calls (jit cache key).
    jax.block_until_ready(rollout_random(
        env, jax.random.PRNGKey(0), console_steps // batch, batch, False)[0])
    jax.block_until_ready(rollout_random(
        env, jax.random.PRNGKey(0), render_steps // batch, batch, True)[0])
    runner = PythonRunner(BASELINES["CartPole-v1"])

    out = {}
    for mode, steps in (("console", console_steps), ("graphical", render_steps)):
        render = mode == "graphical"
        cairl = _measure(lambda: jax.block_until_ready(
            rollout_random(env, jax.random.PRNGKey(1), steps // batch, batch, render)[0]))
        gym_steps = min(steps, 20_000 if not render else 400)
        gym = _measure(lambda: runner.run(gym_steps, render=render))
        gym = type(gym)(wall_s=gym.wall_s * steps / gym_steps,
                        cpu_s=gym.cpu_s * steps / gym_steps)  # scale to equal steps
        out[mode] = {
            "cairl_co2_kg": cairl.co2_kg, "gym_co2_kg": gym.co2_kg,
            "cairl_mwh": cairl.energy_mwh, "gym_mwh": gym.energy_mwh,
            "ratio": gym.co2_kg / max(cairl.co2_kg, 1e-12),
        }
    return out


def main(emit):
    r = run()
    for mode, row in r.items():
        emit(f"table2/{mode}/co2", row["cairl_co2_kg"] * 1e9,
             f"cairl={row['cairl_co2_kg']:.2e}kg gym={row['gym_co2_kg']:.2e}kg "
             f"ratio={row['ratio']:.1f}x (paper: {'20.9x' if mode == 'console' else '1.5e5x'})")


def static_rows(cost_report: dict) -> dict:
    """Per-id static joules/gCO₂ rows from a `repro.analysis.cost` report.

    One row per registry id (plus the fused-train cells, keyed by their
    "<algo>/<env>" id): the pallas cell where hosted, else vmap — the
    backend `make_vec(backend="auto")` would dispatch.
    """
    best: dict = {}
    for r in cost_report["rows"]:
        if r["status"] != "ok":
            continue
        prev = best.get(r["id"])
        if prev is None or (prev["backend"] != "pallas"
                            and r["backend"] == "pallas"):
            best[r["id"]] = r
    return {
        rid: {
            "backend": r["backend"],
            "family": r["family"],
            "flops_per_step": r["flops_per_step"],
            "bytes_per_step": r["bytes_per_step"],
            "dominant": r["roofline"]["dominant"],
            "joules_per_mstep": r["static_impact"]["joules_per_mstep"],
            "co2_g_per_mstep": r["static_impact"]["co2_g_per_mstep"],
        }
        for rid, r in sorted(best.items())
    }


def _cli(argv=None) -> int:
    """`make bench-json` entry: measured Table II rows + the static per-id
    joules/gCO₂ analogue derived from the compiled-cost report."""
    import argparse
    import json

    ap = argparse.ArgumentParser(
        prog="python benchmarks/table2_carbon.py",
        description="Table II energy/CO₂: measured (impact tracker) + "
                    "static (compiled-cost model) rows")
    ap.add_argument("--smoke", action="store_true",
                    help="small step budgets (the make bench-json mode)")
    ap.add_argument("--static-from", default="BENCH_cost_baseline-candidate.json",
                    metavar="COST_JSON",
                    help="cost report to derive the static rows from "
                         "(written by `repro.analysis.cost --json`)")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write the combined table as JSON")
    args = ap.parse_args(argv)
    measured = (run(console_steps=16_000, render_steps=320) if args.smoke
                else run())
    try:
        with open(args.static_from) as f:
            static = static_rows(json.load(f))
    except FileNotFoundError:
        print(f"table2: no cost report at {args.static_from}; run "
              "`python -m repro.analysis.cost --smoke --json "
              f"{args.static_from}` first — emitting measured rows only")
        static = {}
    out = {
        "meta": {"smoke": args.smoke, "static_from": args.static_from,
                 "static_ids": len(static)},
        "measured": measured,
        "static": static,
    }
    for mode, row in measured.items():
        print(f"table2/{mode}: cairl={row['cairl_co2_kg']:.2e}kg "
              f"gym={row['gym_co2_kg']:.2e}kg ratio={row['ratio']:.1f}x")
    if static:
        worst = max(static.items(),
                    key=lambda kv: kv[1]["joules_per_mstep"])
        print(f"table2/static: {len(static)} ids, costliest {worst[0]} at "
              f"{worst[1]['joules_per_mstep']:.3g} J/Mstep "
              f"({worst[1]['co2_g_per_mstep']:.3g} gCO₂/Mstep)")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
        print(f"table2: wrote {args.json}")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(_cli())
