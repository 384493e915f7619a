"""Async env serving: continuous slot refill vs lock-step wave serving.

EnvPool's async mode exists for the serving workload: thousands of client
sessions with *heterogeneous* episode budgets multiplexed onto one
accelerator batch. A lock-step pool must serve them in waves — admit
`num_slots` sessions, step every lane until the LONGEST budget in the wave
finishes, repeat — so short sessions burn dead lane-steps waiting for the
stragglers. The async pool (repro.pool.AsyncEnvPool + serving.EnvService)
retires each session the tick its budget is spent and splices the next
queued session's reset state into the freed slot, keeping occupancy high.

This benchmark replays the SAME synthetic traffic (sessions with budgets
drawn from a long-tailed mixture) through both schedulers and reports:

  - useful steps/s  (session steps actually served, not lane-steps burned)
  - p50/p99 recv latency per scheduler tick
  - occupancy       (served steps / (ticks * slots))

Device residency is verified, not assumed: the async pool's compiled
masked-step core must contain zero host-transfer instructions
(repro.launch.hlo_analysis.host_transfer_ops).

Run: PYTHONPATH=src python benchmarks/fig_async.py [--smoke]
     [--sessions 2000] [--slots 256] [--json BENCH_fig_async.json]
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import numpy as np

from repro.launch.hlo_analysis import host_transfer_ops
from repro.pool import make_vec
from repro.serving.env_service import EnvService, Session
from repro.serving.slots import percentile


def session_budgets(num_sessions: int, seed: int = 0,
                    short: int = 8, long: int = 128) -> List[int]:
    """Long-tailed budget mixture: mostly short sessions, a slow tail.

    This is the shape that hurts lock-step serving most — one `long` session
    per wave pins every lane for `long` ticks.
    """
    rng = np.random.default_rng(seed)
    budgets = rng.integers(1, short + 1, size=num_sessions)
    tail = rng.random(num_sessions) < 0.1
    budgets[tail] = rng.integers(short, long + 1, size=int(tail.sum()))
    return [int(b) for b in budgets]


def run_async(env: str, slots: int, budgets: List[int]) -> Dict:
    svc = EnvService(env, slots, backend="auto")
    # warm the compiled cores (init / admit / masked step) before timing
    svc.submit(Session(sid=-1, seed=0, num_steps=1))
    svc.run()
    svc.ticks = svc.steps_served = 0
    svc.recv_latencies.clear()

    for i, b in enumerate(budgets):
        svc.submit(Session(sid=i, seed=i, num_steps=b))
    t0 = time.perf_counter()
    svc.run()
    wall = time.perf_counter() - t0
    st = svc.stats()
    assert st["running"] == 0 and st["queued"] == 0
    assert svc.steps_served == sum(budgets)
    return {
        "scheduler": "async-refill",
        "steps_per_s": svc.steps_served / wall,
        "recv_p50_ms": 1e3 * st["recv_p50_s"],
        "recv_p99_ms": 1e3 * st["recv_p99_s"],
        "ticks": st["ticks"],
        "occupancy": svc.steps_served / (st["ticks"] * slots),
        "wall_s": wall,
    }


def run_lockstep(env: str, slots: int, budgets: List[int]) -> Dict:
    """Wave serving on the lock-step pool: the whole batch steps together,
    so each wave runs for max(budgets-in-wave) ticks and a lane whose
    session finished early burns dead steps until the wave ends."""
    pool = make_vec(env, slots, backend="auto")
    rng = np.random.default_rng(0)
    pool.reset(seed=0)
    pool.step(np.asarray(pool.sample_actions(0)))  # warm the compiled step

    served = ticks = 0
    recv_lat: List[float] = []
    t0 = time.perf_counter()
    for wave_start in range(0, len(budgets), slots):
        wave = budgets[wave_start:wave_start + slots]
        pool.reset(seed=wave_start)
        for t in range(max(wave)):
            acts = np.asarray(pool.sample_actions(rng.integers(1 << 31)))
            s0 = time.perf_counter()
            pool.step(acts)
            recv_lat.append(time.perf_counter() - s0)
            ticks += 1
            served += sum(1 for b in wave if t < b)
    wall = time.perf_counter() - t0
    assert served == sum(budgets)
    return {
        "scheduler": "lock-step-waves",
        "steps_per_s": served / wall,
        "recv_p50_ms": 1e3 * percentile(recv_lat, 50),
        "recv_p99_ms": 1e3 * percentile(recv_lat, 99),
        "ticks": ticks,
        "occupancy": served / (ticks * slots),
        "wall_s": wall,
    }


def check_device_resident(env: str, slots: int) -> List[str]:
    """Host-transfer instructions in the async pool's compiled masked-step
    core (must be empty: send/recv bookkeeping is host-side, the env step
    itself never leaves the device)."""
    pool = make_vec(env, slots, backend="async")
    return host_transfer_ops(pool.step_lowered().compile().as_text())


def run(env: str = "CartPole-v1", sessions: int = 2000, slots: int = 256,
        seed: int = 0) -> Dict:
    budgets = session_budgets(sessions, seed=seed)
    transfers = check_device_resident(env, slots)
    rows = {
        "async": run_async(env, slots, budgets),
        "lockstep": run_lockstep(env, slots, budgets),
    }
    for r in rows.values():
        r["host_transfers"] = len(transfers)
        r["transfer_ops"] = transfers
    rows["async"]["speedup_vs_lockstep"] = (
        rows["async"]["steps_per_s"] / rows["lockstep"]["steps_per_s"])
    return {"env": env, "sessions": sessions, "slots": slots,
            "total_steps": sum(budgets), "rows": rows}


def main(emit):
    out = run(sessions=200, slots=32)
    for name, r in out["rows"].items():
        assert r["host_transfers"] == 0, (name, r)
        emit(f"fig_async/{name}", 1e6 / r["steps_per_s"],
             f"steps_per_s={r['steps_per_s']:.0f};"
             f"recv_p99_ms={r['recv_p99_ms']:.2f};"
             f"occupancy={r['occupancy']:.2f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="CartPole-v1")
    ap.add_argument("--sessions", type=int, default=2000)
    ap.add_argument("--slots", type=int, default=256)
    ap.add_argument("--smoke", action="store_true",
                    help="small traffic (200 sessions / 32 slots)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write per-scheduler rows as JSON (bench-json)")
    args = ap.parse_args()
    if args.smoke:
        args.sessions, args.slots = 200, 32

    print(f"devices: {len(jax.devices())} ({jax.default_backend()})")
    out = run(args.env, args.sessions, args.slots)
    for name, r in out["rows"].items():
        resident = "device-resident" if r["host_transfers"] == 0 else \
            f"HOST TRANSFERS: {r['transfer_ops']}"
        print(f"{r['scheduler']:>16}: {r['steps_per_s']:>10,.0f} steps/s  "
              f"p50 {r['recv_p50_ms']:6.2f}ms  p99 {r['recv_p99_ms']:6.2f}ms  "
              f"occupancy {r['occupancy']:.2f}  [{resident}]")
    print(f"async speedup vs lock-step waves: "
          f"{out['rows']['async']['speedup_vs_lockstep']:.2f}x")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json}")
