"""Fig. 4 (new): EnvPool batch-size / device-count scaling sweep.

EnvPool's headline result is that throughput scales with the env batch until
the accelerator saturates; Jumanji's is that pure-functional envs extend the
curve across a device mesh. This sweep measures both axes for the compiled
pool:

  - batch axis   : EnvPool steps/s for batch sizes {1, 64, 1024} (default)
  - device axis  : ShardedEnvPool steps/s for device counts {1, ..., N}
                   (only the counts this host exposes; set
                   REPRO_FORCE_DEVICES=8 to fake an 8-device CPU mesh)

Device residency is *verified*, not assumed: the scanned step loop's
optimized HLO must contain zero host-transfer instructions
(repro.launch.hlo_analysis.host_transfer_ops). Every pool — plain, fused
and sharded — is built through the unified `repro.make_vec` frontend.

Run: PYTHONPATH=src python benchmarks/fig4_pool_scaling.py
     [--steps 2000] [--batches 1,64,1024] [--env CartPole-v1]
"""
from __future__ import annotations

import os

# Must precede the first jax import to take effect (benchmark-only knob).
_FORCE = os.environ.get("REPRO_FORCE_DEVICES")
if _FORCE and "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={_FORCE}")

import time
from typing import Dict, List

import jax

from repro.launch.hlo_analysis import host_transfer_ops
from repro.pool import default_pool_mesh, make_vec


def bench_pool(pool, steps: int, trials: int = 3) -> float:
    jax.block_until_ready(pool.rollout(steps, jax.random.PRNGKey(0))[0])  # compile
    best = 0.0
    for t in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(pool.rollout(steps, jax.random.PRNGKey(t + 1))[0])
        best = max(best, steps * pool.num_envs / (time.perf_counter() - t0))
    return best


def check_device_resident(pool, steps: int = 64) -> List[str]:
    """Host-transfer instructions in the compiled rollout (must be empty)."""
    compiled = pool.rollout_lowered(steps).compile()
    return host_transfer_ops(compiled.as_text())


def run(env_name: str = "CartPole-v1", steps: int = 2000,
        batches=(1, 64, 1024), unroll: int = 32) -> Dict:
    rows: Dict[str, Dict] = {}
    for batch in batches:
        pool = make_vec(env_name, batch, backend="vmap")
        transfers = check_device_resident(pool)
        rows[f"batch{batch}"] = {
            "steps_per_s": bench_pool(pool, steps),
            "host_transfers": len(transfers),
            "transfer_ops": transfers,
        }
    # Fused megastep engine over the same batch axis (kernels/envstep):
    # one kernel launch per `unroll` steps instead of a scanned vmap step.
    # Envs without a fused spec (e.g. Multitask) just skip these rows.
    from repro.core.env import supports_fused_step
    from repro.core.registry import make

    if supports_fused_step(make(env_name)):
        for batch in batches:
            pool = make_vec(env_name, batch, backend="pallas",
                            unroll=unroll)
            transfers = check_device_resident(pool)
            rows[f"pallas_batch{batch}"] = {
                "steps_per_s": bench_pool(pool, steps),
                "host_transfers": len(transfers),
                "transfer_ops": transfers,
                "unroll": unroll,
            }

    # Arcade pixel workload: fused megastep game logic + per-chunk on-device
    # rendering — the heavy-env case where pooled execution pays off most.
    if env_name == "CartPole-v1":
        pixel_batch = min(64, max(batches))
        pool = make_vec("Pong-v0", pixel_batch, backend="pallas", unroll=8)
        rows[f"pixel_pong_batch{pixel_batch}"] = {
            "steps_per_s": bench_pool(pool, min(steps, 500)),
            "batch": pixel_batch,
            "host_transfers": len(check_device_resident(pool, steps=32)),
            "unroll": 8,
        }

    n_dev = len(jax.devices())
    dev_counts = sorted({1, n_dev} | ({2} if n_dev >= 2 else set()))
    base = max(batches)
    for d in dev_counts:
        dev_batch = base - base % d or d  # round down to divide d; min d
        pool = make_vec(env_name, dev_batch, backend="vmap",
                        mesh=default_pool_mesh(d))
        rows[f"devices{d}"] = {
            "steps_per_s": bench_pool(pool, steps),
            "batch": dev_batch,
            "host_transfers": len(check_device_resident(pool)),
        }
    return rows


def main(emit):
    rows = run(steps=500, batches=(1, 64, 1024))
    for name, r in rows.items():
        assert r["host_transfers"] == 0, (name, r)
        extra = f";batch={r['batch']}" if "batch" in r else ""
        emit(f"fig4/{name}", 1e6 / r["steps_per_s"],
             f"steps_per_s={r['steps_per_s']:.0f};host_transfers=0{extra}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="CartPole-v1")
    ap.add_argument("--steps", type=int, default=2000)
    ap.add_argument("--batches", default="1,64,1024")
    ap.add_argument("--unroll", type=int, default=32,
                    help="env steps fused per megastep launch (pallas rows)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write steps/sec per config as JSON (bench-json)")
    args = ap.parse_args()
    batches = tuple(int(b) for b in args.batches.split(","))

    print(f"devices: {len(jax.devices())} ({jax.default_backend()})")
    rows = run(args.env, args.steps, batches, unroll=args.unroll)
    for name, r in rows.items():
        resident = "device-resident" if r["host_transfers"] == 0 else \
            f"HOST TRANSFERS: {r['transfer_ops']}"
        print(f"{name:>16}: {r['steps_per_s']:>12,.0f} steps/s  [{resident}]")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"env": args.env, "steps": args.steps,
                       "unroll": args.unroll, "rows": rows}, f, indent=2)
        print(f"wrote {args.json}")
