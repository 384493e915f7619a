"""Fig. 1 reproduction: env execution throughput, CaiRL vs interpreted Gym.

Paper setup: 100 000 steps averaged over trials, console and render modes,
four classic-control envs. Here both execution models run behind the same
pool API (repro.pool): `EnvPool` compiles the whole batched rollout into one
device program; `HostPool` drives the pure-Python baselines (same dynamics,
same machine). Reported: steps/s both ways and the ratio (paper: ~5×
console, ~80× render).
"""
from __future__ import annotations

import json
import time
from typing import Dict

import jax

from repro.launch.hlo_analysis import host_transfer_ops
from repro.pool import EnvPool, make_vec

ENVS = ["CartPole-v1", "Acrobot-v1", "MountainCar-v0", "Pendulum-v1"]
# Arcade pixel games: every step renders 84×84 observations on device, the
# paper's software-rendering workload (§II-B) — console mode is render mode.
ARCADE = ["Pong-v0"]
# Procedural gridworlds (envs/grid): the level regenerates every episode on
# the autoreset key chain, so console throughput includes on-device level
# generation; the interpreted comparator regenerates with python RNG.
GRID = ["FrozenLake-v0", "CliffWalk-v0", "Snake-v0", "Maze-v0"]


def bench_compiled(name: str, steps: int, batch: int, render: bool,
                   trials: int = 3, backend: str = "vmap",
                   unroll: int = 32) -> float:
    pool = make_vec(name, batch, backend=backend, unroll=unroll)
    jax.block_until_ready(pool.rollout(steps, jax.random.PRNGKey(0), render)[0])  # compile
    best = 0.0
    for t in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(pool.rollout(steps, jax.random.PRNGKey(t), render)[0])
        sps = steps * batch / (time.perf_counter() - t0)
        best = max(best, sps)
    return best


def bench_python(name: str, steps: int, render: bool, trials: int = 2) -> float:
    pool = make_vec(name, 1, host=True)
    best = 0.0
    for t in range(trials):
        t0 = time.perf_counter()
        pool.run_random(steps, seed=t, render=render)
        sps = steps / (time.perf_counter() - t0)
        best = max(best, sps)
    return best


def run(console_steps: int = 2000, render_steps: int = 200, batch: int = 64) -> Dict:
    rows = {}
    for name in ENVS + ARCADE + GRID:
        # Arcade ids observe rendered frames, so their compiled "console"
        # mode rasterises every step — the interpreted comparator must
        # render too or the ratio measures rendering-vs-nothing.
        pixel = name in ARCADE
        p_steps = max(console_steps // 4, 25) if pixel else console_steps
        c_sps = bench_compiled(name, console_steps, batch, render=False)
        p_sps = bench_python(name, p_steps, render=pixel)
        cr_sps = bench_compiled(name, render_steps, batch, render=True)
        pr_sps = bench_python(name, max(render_steps // 4, 25), render=True)
        rows[name] = {
            "cairl_console_sps": c_sps,
            "gym_console_sps": p_sps,
            "console_speedup": c_sps / p_sps,
            "cairl_render_sps": cr_sps,
            "gym_render_sps": pr_sps,
            "render_speedup": cr_sps / pr_sps,
        }
    return rows


def run_backends(steps: int = 2000, batch: int = 64, unroll: int = 32,
                 include_host: bool = True, envs=None,
                 backends=("vmap", "pallas")) -> Dict:
    """Per-backend console throughput: vmap pool vs fused pallas megastep.

    The pallas pool's compiled rollout is also HLO-checked for host
    transfers (must be 0 — device residency survives the fused path).
    Arcade pixel envs run with a capped unroll: every fused chunk
    materialises K·B rendered frames, so deep unrolls trade throughput for
    framebuffer memory.
    """
    from repro.core.registry import make

    rows: Dict[str, Dict] = {}
    for name in (envs or ENVS + ARCADE + GRID):
        r: Dict = {}
        pixel = len(make(name).observation_space.shape) >= 2
        u = min(unroll, 8) if pixel else unroll
        if "vmap" in backends:
            r["vmap_sps"] = bench_compiled(name, steps, batch, render=False)
        if "pallas" in backends:
            pool = make_vec(name, batch, backend="pallas", unroll=u)
            transfers = host_transfer_ops(
                pool.rollout_lowered(min(steps, 256)).compile().as_text())
            r["host_transfers"] = len(transfers)
            r["pallas_sps"] = bench_compiled(name, steps, batch, render=False,
                                             backend="pallas", unroll=u)
        if "vmap_sps" in r and "pallas_sps" in r:
            r["pallas_vs_vmap"] = r["pallas_sps"] / r["vmap_sps"]
        if include_host:
            # Pixel envs: the interpreted side renders too (see run()).
            h_steps = min(steps, 500) if pixel else min(steps, 2000)
            r["gym_sps"] = bench_python(name, h_steps, render=pixel)
        rows[name] = r
    return rows


def bench_frontend(name: str = "CartPole-v1", batch: int = 64,
                   steps: int = 500, trials: int = 3) -> Dict:
    """Frontend-overhead row: `make_vec` vs raw `EnvPool` construction.

    Measures (a) constructor + first-step compile wall-clock and (b)
    steady-state steps/s through each constructor, on the same vmap step
    engine — the evidence that the declarative `EnvSpec`/`make_vec` frontend
    is construction-time-only and adds no steady-state cost.
    """
    import numpy as np

    def once(ctor):
        t0 = time.perf_counter()
        pool = ctor()
        pool.reset(seed=0)
        jax.block_until_ready(pool.step(pool.sample_actions(0))[0])
        startup_s = time.perf_counter() - t0
        jax.block_until_ready(pool.rollout(steps, jax.random.PRNGKey(0))[0])
        best = 0.0
        for t in range(trials):
            t0 = time.perf_counter()
            jax.block_until_ready(
                pool.rollout(steps, jax.random.PRNGKey(t + 1))[0])
            best = max(best, steps * batch / (time.perf_counter() - t0))
        return startup_s, best

    mv_start, mv_sps = once(lambda: make_vec(name, batch, backend="vmap"))
    raw_start, raw_sps = once(lambda: EnvPool(name, batch, backend="vmap"))
    return {
        "env": name, "batch": batch, "steps": steps,
        "make_vec_startup_s": mv_start, "envpool_startup_s": raw_start,
        "make_vec_sps": mv_sps, "envpool_sps": raw_sps,
        "steady_state_ratio": mv_sps / raw_sps if raw_sps else float(np.nan),
    }


def main(emit):
    rows = run()
    for name, r in rows.items():
        emit(f"fig1/{name}/console", 1e6 / r["cairl_console_sps"],
             f"speedup={r['console_speedup']:.1f}x (cairl {r['cairl_console_sps']:.0f} vs gym {r['gym_console_sps']:.0f} steps/s)")
        emit(f"fig1/{name}/render", 1e6 / r["cairl_render_sps"],
             f"speedup={r['render_speedup']:.1f}x (cairl {r['cairl_render_sps']:.0f} vs gym {r['gym_render_sps']:.0f} steps/s)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default="both",
                    choices=["vmap", "pallas", "both"],
                    help="pool step engine(s) to benchmark")
    ap.add_argument("--steps", type=int, default=2000)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--unroll", type=int, default=32,
                    help="env steps fused per megastep kernel launch")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write steps/sec per backend as JSON (bench-json)")
    ap.add_argument("--smoke", action="store_true",
                    help="small step counts for CI smoke / perf trajectory")
    args = ap.parse_args()
    if args.smoke:
        args.steps = min(args.steps, 300)

    # --backend pallas still measures vmap: the deliverable is the ratio.
    backends = ("vmap",) if args.backend == "vmap" else ("vmap", "pallas")
    print(f"devices: {len(jax.devices())} ({jax.default_backend()})  "
          f"steps={args.steps} batch={args.batch} unroll={args.unroll}")
    rows = run_backends(args.steps, args.batch, args.unroll,
                        include_host=not args.smoke, backends=backends)
    frontend = bench_frontend(batch=args.batch, steps=min(args.steps, 500))
    print(f"{'frontend':>16}: make_vec {frontend['make_vec_sps']:>12,.0f} "
          f"steps/s vs EnvPool {frontend['envpool_sps']:>12,.0f} "
          f"({frontend['steady_state_ratio']:.2f}x steady-state; startup "
          f"{frontend['make_vec_startup_s']:.2f}s vs "
          f"{frontend['envpool_startup_s']:.2f}s)")
    for name, r in rows.items():
        line = f"{name:>16}: vmap {r['vmap_sps']:>12,.0f} steps/s"
        if "pallas_sps" in r:
            resident = ("device-resident" if r["host_transfers"] == 0
                        else f"HOST TRANSFERS: {r['host_transfers']}")
            line += (f" | pallas {r['pallas_sps']:>12,.0f} steps/s "
                     f"({r['pallas_vs_vmap']:.2f}x) [{resident}]")
        if "gym_sps" in r:
            line += f" | gym {r['gym_sps']:,.0f}"
        print(line)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"steps": args.steps, "batch": args.batch,
                       "unroll": args.unroll,
                       "backend_filter": args.backend, "envs": rows,
                       "frontend_overhead": frontend}, f, indent=2)
        print(f"wrote {args.json}")
