"""Fault-tolerance cost: what does surviving failures actually cost?

Three numbers decide whether checkpointed rollouts are affordable:

  1. steady-state tax   — steps/s with the RolloutSupervisor snapshotting
                          vs the bare pool (same compiled step; the only
                          added work is the boundary gather + async write);
  2. snapshot cost      — per-snapshot gather/save wall time as a function
                          of the snapshot interval (amortization curve);
  3. recovery time      — wall time from an injected device loss to a
                          restored, stepping pool (propose_mesh + rebuild
                          + restore), plus the replay debt in steps.

Device residency is verified, not assumed: the supervised steady-state
step is the pool's own compiled step (the supervisor only intercepts on
the host), and its HLO must contain zero host-transfer instructions.

Run: PYTHONPATH=src python benchmarks/fig_fault.py [--smoke]
     [--batch 1024] [--steps 2000] [--json BENCH_fig_fault.json]
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import numpy as np

from repro.launch.hlo_analysis import host_transfer_ops
from repro.pool import EnvPool
from repro.runtime import DeviceLossError, FaultInjector, RolloutSupervisor


def _actions(pool, steps: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    shape = (steps, pool.num_envs) + tuple(pool.action_space.shape)
    return rng.integers(0, pool.action_space.n, size=shape).astype(
        pool.action_space.dtype)


def run_steady(env: str, batch: int, steps: int, snapshot_every: int,
               ckpt_dir: str) -> Dict:
    """Supervised rollout throughput; snapshot_every=0 disables snapshots
    (the bare-pool baseline through the same supervisor host path)."""
    pool = EnvPool(env, batch)
    sup = RolloutSupervisor(pool, ckpt_dir, snapshot_every=snapshot_every)
    acts = _actions(pool, steps)
    sup.reset(seed=0)
    sup.step(acts[0])                      # warm the compiled step
    sup.reset(seed=0)
    t0 = time.perf_counter()
    for t in range(steps):
        obs, _, _, _ = sup.step(acts[t])
    jax.block_until_ready(obs)
    sup.manager.wait()                     # the tax includes joining writes
    wall = time.perf_counter() - t0
    return {
        "snapshot_every": snapshot_every,
        "snapshots": sup.snapshots,
        "steps_per_s": steps * batch / wall,
        "wall_s": wall,
    }


def run_snapshot_cost(env: str, batch: int, intervals: List[int],
                      ckpt_dir: str, reps: int = 5) -> List[Dict]:
    """Per-snapshot blocking cost (gather + atomic write) and the implied
    per-step amortized overhead at each interval."""
    pool = EnvPool(env, batch)
    sup = RolloutSupervisor(pool, ckpt_dir, snapshot_every=0)
    sup.reset(seed=0)
    sup.step(_actions(pool, 1)[0])
    sup.snapshot(blocking=True)            # warm the save path
    t0 = time.perf_counter()
    for _ in range(reps):
        sup.snapshot(blocking=True)
    per_snap = (time.perf_counter() - t0) / reps
    return [{"interval": k, "snapshot_s": per_snap,
             "amortized_ms_per_step": 1e3 * per_snap / k}
            for k in intervals]


def run_recovery(env: str, batch: int, ckpt_dir: str,
                 snapshot_every: int = 64) -> Dict:
    """Injected device loss mid-rollout: time from the raise to a restored
    pool that has re-stepped once, plus the replay debt (steps lost back
    to the snapshot boundary)."""
    clk = [0.0]
    inj = FaultInjector(clock=lambda: clk[0])
    pool = EnvPool(env, batch)
    sup = RolloutSupervisor(pool, ckpt_dir, snapshot_every=snapshot_every,
                            blocking_snapshots=True, injector=inj)
    acts = _actions(pool, snapshot_every + snapshot_every // 2 + 1)
    sup.reset(seed=0)
    for t in range(snapshot_every + snapshot_every // 2):
        sup.step(acts[t])
    t_kill = sup.t
    inj.schedule(1.0, "device_loss", 1)
    clk[0] = 2.0
    t0 = time.perf_counter()
    try:
        sup.step(acts[t_kill])
        raise AssertionError("device-loss fault did not fire")
    except DeviceLossError:
        sup.recover()
        obs, _, _, _ = sup.step(acts[sup.t])   # first post-recovery step
        jax.block_until_ready(obs)
    recovery_s = time.perf_counter() - t0
    return {
        "killed_at_step": t_kill,
        "restored_step": t_kill - t_kill % snapshot_every,
        "replay_debt_steps": t_kill % snapshot_every,
        "recovery_s": recovery_s,
    }


def check_device_resident(env: str, batch: int, ckpt_dir: str) -> List[str]:
    sup = RolloutSupervisor(EnvPool(env, batch), ckpt_dir)
    return host_transfer_ops(sup.step_lowered().compile().as_text())


def run(env: str = "CartPole-v1", batch: int = 1024, steps: int = 2000,
        intervals: List[int] = (16, 64, 256)) -> Dict:
    import tempfile

    transfers = check_device_resident(env, batch, tempfile.mkdtemp())
    rows = {
        "ckpt_off": run_steady(env, batch, steps, 0, tempfile.mkdtemp()),
        "ckpt_on": run_steady(env, batch, steps, max(intervals[0], 1),
                              tempfile.mkdtemp()),
        "recovery": run_recovery(env, batch, tempfile.mkdtemp()),
        "snapshot_cost": run_snapshot_cost(env, batch, list(intervals),
                                           tempfile.mkdtemp()),
    }
    on, off = rows["ckpt_on"], rows["ckpt_off"]
    on["overhead_pct"] = 100.0 * (1.0 - on["steps_per_s"] / off["steps_per_s"])
    return {"env": env, "batch": batch, "steps": steps,
            "host_transfers": len(transfers), "transfer_ops": transfers,
            "rows": rows}


def main(emit):
    out = run(batch=256, steps=400, intervals=[8, 32, 128])
    assert out["host_transfers"] == 0, out["transfer_ops"]
    for name in ("ckpt_off", "ckpt_on"):
        r = out["rows"][name]
        emit(f"fig_fault/{name}", 1e6 / r["steps_per_s"],
             f"steps_per_s={r['steps_per_s']:.0f};"
             f"snapshots={r['snapshots']}")
    rec = out["rows"]["recovery"]
    emit("fig_fault/recovery", rec["recovery_s"] * 1e3,
         f"recovery_s={rec['recovery_s']:.3f};"
         f"replay_debt={rec['replay_debt_steps']}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="CartPole-v1")
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=2000)
    ap.add_argument("--smoke", action="store_true",
                    help="small run (batch 256 / 400 steps)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write rows as JSON (bench-json)")
    args = ap.parse_args()
    if args.smoke:
        args.batch, args.steps = 256, 400

    print(f"devices: {len(jax.devices())} ({jax.default_backend()})")
    out = run(args.env, args.batch, args.steps)
    resident = ("device-resident" if out["host_transfers"] == 0
                else f"HOST TRANSFERS: {out['transfer_ops']}")
    off, on = out["rows"]["ckpt_off"], out["rows"]["ckpt_on"]
    print(f"   checkpoint off: {off['steps_per_s']:>12,.0f} steps/s")
    print(f"    checkpoint on: {on['steps_per_s']:>12,.0f} steps/s  "
          f"(every {on['snapshot_every']} steps, {on['snapshots']} snapshots, "
          f"{on['overhead_pct']:.1f}% tax)  [{resident}]")
    rec = out["rows"]["recovery"]
    print(f"  device-loss recovery: {rec['recovery_s']*1e3:.0f} ms "
          f"(+{rec['replay_debt_steps']} steps replay debt)")
    for row in out["rows"]["snapshot_cost"]:
        print(f"  snapshot every {row['interval']:>4}: "
              f"{row['snapshot_s']*1e3:7.1f} ms/snap  "
              f"{row['amortized_ms_per_step']:6.3f} ms/step amortized")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json}")
