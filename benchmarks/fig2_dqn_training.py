"""Fig. 2 reproduction: DQN wall-clock, CaiRL envs vs interpreted envs.

Paper: identical DQN (Table I), training until convergence; CaiRL cuts
~30 % of wall-clock because env stepping leaves the critical path. Here:
identical jitted learner, fixed step budget; execution model is the only
variable, across three rungs of host involvement:

  gym      — per-step interpreted host env (the AI-Gym execution model);
  compiled — env/replay/learner compiled, but the training loop dispatches
             host-alternating chunks (`train_compiled`, several jits);
  fused    — the whole chunk is ONE donated device program
             (`train_compiled(fused=True)` via repro.train.fused): replay
             ring, optimizer state and key chain updated in place, zero
             host transfers inside the chunk (gated by analysis/audit).

Plus the fleet-scaling rows: `repro.train.fleet` vmaps the ENTIRE training
loop over a seeds axis, so a width-F sweep is one compiled batch. The
sublinearity claim — wall-clock(F) < F x wall-clock(1) — is recorded per
width (`speedup_vs_sequential`).

`python benchmarks/fig2_dqn_training.py --smoke --json BENCH_fig2.json`
"""
from __future__ import annotations

import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro.configs.cairl_dqn import PAPER_TABLE_I
from repro.core import make
from repro.envs.baseline_python import BASELINES
from repro.rl.dqn import train_compiled, train_host
from repro.train.fused import Fleet, fleet

FLEET_WIDTHS = (1, 2, 4, 8)


def _cfg(num_envs: int = 1):
    return dataclasses.replace(PAPER_TABLE_I, num_envs=num_envs,
                               learn_start=100)


def run(steps: int = 2000, include_host: bool = True):
    """The execution-model comparison (one row per rung, seconds)."""
    env = make("CartPole-v1")
    cfg = _cfg()
    rows = {"steps": steps}

    t0 = time.perf_counter()
    state, _, _ = train_compiled(env, cfg, steps, jax.random.PRNGKey(0),
                                 chunk=max(steps // 8, 1))
    jax.block_until_ready(state)
    rows["compiled_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    state, _, _ = train_compiled(env, cfg, steps, jax.random.PRNGKey(0),
                                 fused=True)
    jax.block_until_ready(state)
    rows["fused_s"] = time.perf_counter() - t0
    rows["fused_vs_compiled"] = rows["compiled_s"] / rows["fused_s"]

    if include_host:
        t0 = time.perf_counter()
        train_host(BASELINES["CartPole-v1"], env, cfg, steps,
                   jax.random.PRNGKey(0))
        rows["gym_s"] = time.perf_counter() - t0
        rows["reduction"] = 1.0 - rows["compiled_s"] / rows["gym_s"]
        rows["fused_reduction"] = 1.0 - rows["fused_s"] / rows["gym_s"]
    return rows


def run_fleet(steps: int = 500, widths=FLEET_WIDTHS):
    """Fleet-scaling rows: one vmapped batch per width (compile included —
    every width is a fresh program, exactly what a user-facing sweep pays).

    `speedup_vs_sequential` = (F x wall-clock(1)) / wall-clock(F); > 1 is
    the sublinearity claim (a fleet beats F sequential solo runs).
    """
    env = make("CartPole-v1")
    cfg = _cfg()
    rows = {"steps": steps, "widths": list(widths), "rows": []}
    per_run_s = None   # wall-clock of one sequential run (first width, /w)
    for w in widths:
        grid = Fleet(jnp.arange(w, dtype=jnp.int32),
                     jnp.full((w,), cfg.lr, jnp.float32))
        t0 = time.perf_counter()
        states, _ = fleet(env, grid, steps, algo="dqn", cfg=cfg)
        jax.block_until_ready(states)
        wall_s = time.perf_counter() - t0
        per_run_s = wall_s / w if per_run_s is None else per_run_s
        rows["rows"].append({
            "width": w,
            "wall_s": wall_s,
            "runs_per_s": w / wall_s,
            "speedup_vs_sequential": (w * per_run_s) / wall_s,
            "sublinear": wall_s < w * per_run_s or w == widths[0],
        })
    return rows


def main(emit):
    r = run()
    emit("fig2/dqn_cartpole/cairl", r["compiled_s"] / r["steps"] * 1e6,
         f"total={r['compiled_s']:.2f}s")
    emit("fig2/dqn_cartpole/fused", r["fused_s"] / r["steps"] * 1e6,
         f"total={r['fused_s']:.2f}s; vs_compiled={r['fused_vs_compiled']:.2f}x")
    emit("fig2/dqn_cartpole/gym", r["gym_s"] / r["steps"] * 1e6,
         f"total={r['gym_s']:.2f}s; wallclock_reduction={r['reduction']*100:.0f}% (paper: ~30%)")
    fl = run_fleet()
    for row in fl["rows"]:
        emit(f"fig2/fleet/width{row['width']}", row["wall_s"] * 1e3,
             f"{row['runs_per_s']:.2f} runs/s; "
             f"{row['speedup_vs_sequential']:.2f}x vs sequential")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=2000,
                    help="train steps per execution-model row")
    ap.add_argument("--fleet-steps", type=int, default=500,
                    help="train steps per fleet-scaling row")
    ap.add_argument("--widths", default=",".join(map(str, FLEET_WIDTHS)),
                    help="comma-separated fleet widths")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the rows as JSON (bench-json)")
    ap.add_argument("--smoke", action="store_true",
                    help="small step counts for CI smoke / perf trajectory")
    args = ap.parse_args()
    if args.smoke:
        args.steps = min(args.steps, 256)
        args.fleet_steps = min(args.fleet_steps, 128)
    widths = tuple(int(w) for w in args.widths.split(",") if w.strip())

    print(f"devices: {len(jax.devices())} ({jax.default_backend()})  "
          f"steps={args.steps} fleet_steps={args.fleet_steps}")
    modes = run(args.steps)
    print(f"  gym (interpreted host env): {modes['gym_s']:7.2f}s")
    print(f"  compiled (host-alternating): {modes['compiled_s']:6.2f}s "
          f"(reduction {modes['reduction'] * 100:.0f}%, paper ~30%)")
    print(f"  fused (one donated program): {modes['fused_s']:6.2f}s "
          f"({modes['fused_vs_compiled']:.2f}x vs compiled, reduction "
          f"{modes['fused_reduction'] * 100:.0f}%)")
    fleet_rows = run_fleet(args.fleet_steps, widths)
    for row in fleet_rows["rows"]:
        tag = "sublinear" if row["sublinear"] else "LINEAR OR WORSE"
        print(f"  fleet width {row['width']:>2}: {row['wall_s']:6.2f}s "
              f"({row['runs_per_s']:.2f} runs/s, "
              f"{row['speedup_vs_sequential']:.2f}x vs sequential) [{tag}]")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"smoke": args.smoke, "modes": modes,
                       "fleet": fleet_rows}, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
