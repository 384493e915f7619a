#!/usr/bin/env python3
"""Chip smoke test: the system's main path, once, on TPU, at deployment sizes.

    python chip_smoke.py             # one chip: pools, fused training, async
    python chip_smoke.py --chips 4   # four chips: the sharded pool only

One process, through the entry points a user calls: `repro.make_vec`, the
pools, `dqn.train_compiled(..., fused=True)` and `ppo.train(..., fused=True)`.

  - pools: CartPole-v1 (4096 envs, unroll 32), Snake-v0 (4096, 32) and
    Pong-v0 (1024, 8; 4x84x84 frames from the megastep plus the
    rasteriser). `backend="auto"` must compile to a Pallas kernel
    (`tpu_custom_call` in the program), and one chunk must match
    `backend="vmap"` at the same batch and key on the chip: floats within
    rtol=atol=1e-4, integer and bool leaves exactly.
  - rasteriser: 1024 84x84 frames, Pallas against the jnp reference.
  - fused training: DQN with the paper's Table I widths (32x32 MLP, 50k
    replay) over 256 envs on the Pallas env engine, and PPO on CartPole-v1
    with the `PPOConfig` defaults; a few chunks each, finite metrics. One
    more DQN chunk of 100 steps is judged by the benchmark's reference
    (`bench/reference/dqn_cartpole.py`) within the `dqn-cartpole-v1`
    configuration's limits, so a learner that drifts from float32 fails.
  - async pool: a few send/recv rounds of CartPole-v1 over 256 slots.
  - `--chips 4`: `ShardedEnvPool` of CartPole-v1 over 16384 envs on a
    4-device mesh, Pallas against vmap on the same mesh, and the carry
    spread over 4 distinct devices. No other phase runs.

Without a TPU it exits non-zero and prints no result. A failing phase is not
caught: the exit code is non-zero. Every phase prints its shapes, device
bytes, compile seconds and wall seconds; these are smoke timings, not
benchmark results. The last line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

TOL = dict(rtol=1e-4, atol=1e-4)  # the golden-trace tolerance

#: (id, num_envs, unroll) of the one-chip pool phases
POOL_PHASES = (("CartPole-v1", 4096, 32), ("Snake-v0", 4096, 32),
               ("Pong-v0", 1024, 8))
SHARDED_PHASE = ("CartPole-v1", 16384, 32)
#: what a compiled program holds where a Pallas kernel runs on the TPU
KERNEL_MARK = "tpu_custom_call"

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


def say(*parts) -> None:
    print("[smoke]", *parts, flush=True)


class Phase:
    """Times one phase: wall seconds, and the compile seconds JAX reports
    through its monitoring events while the phase runs."""

    compile_s = 0.0

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        Phase.compile_s = 0.0
        self.t0 = time.perf_counter()
        say(f"phase {self.name}")
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False  # the failure propagates: exit code non-zero
        wall = time.perf_counter() - self.t0
        say(f"  compile seconds: {Phase.compile_s:.3f} (smoke timing)")
        say(f"  wall seconds: {wall:.3f} (smoke timing, not a benchmark "
            "result)")
        say(f"  {self.name}: PASS")
        return False


def _on_compile_event(event: str, seconds: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        Phase.compile_s += seconds


def device_bytes(compiled=None) -> str:
    import jax

    parts = []
    if compiled is not None:
        ma = compiled.memory_analysis()
        parts.append(f"program args={ma.argument_size_in_bytes} "
                     f"out={ma.output_size_in_bytes} "
                     f"temp={ma.temp_size_in_bytes}")
    stats = jax.devices()[0].memory_stats() or {}
    parts.append(f"device0 in_use={stats.get('bytes_in_use')} "
                 f"peak={stats.get('peak_bytes_in_use')}")
    return "; ".join(parts)


def shapes(tree) -> str:
    import jax

    return ", ".join(f"{jax.tree_util.keystr(p)}{tuple(x.shape)}:{x.dtype}"
                     for p, x in jax.tree_util.tree_leaves_with_path(tree))


def assert_finite(tree, what: str) -> None:
    import jax
    import jax.numpy as jnp

    for p, x in jax.tree_util.tree_leaves_with_path(tree):
        if jnp.issubdtype(x.dtype, jnp.floating):
            assert bool(jnp.all(jnp.isfinite(x))), \
                f"{what}{jax.tree_util.keystr(p)}: non-finite values"


def assert_match(ref, got, what: str) -> float:
    """Golden-tolerance parity, reduced on the device; returns the largest
    float difference."""
    import jax
    import jax.numpy as jnp

    assert jax.tree.structure(ref) == jax.tree.structure(got), what
    worst = 0.0
    for (p, a), b in zip(jax.tree_util.tree_leaves_with_path(ref),
                         jax.tree.leaves(got)):
        name = f"{what}{jax.tree_util.keystr(p)}"
        assert a.shape == b.shape and a.dtype == b.dtype, \
            (name, a.shape, b.shape, a.dtype, b.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating):
            diff = jnp.abs(a - b)
            ok = jnp.all(diff <= TOL["atol"] + TOL["rtol"] * jnp.abs(a))
            worst = max(worst, float(jnp.max(diff)) if a.size else 0.0)
            assert bool(ok), f"{name}: max abs diff {float(jnp.max(diff))}"
        else:
            assert bool(jnp.all(a == b)), f"{name}: integer/bool mismatch"
    return worst


def pool_phase(env_id: str, num_envs: int, unroll: int, *, mesh=None,
               seed: int = 0):
    """One fused chunk of `make_vec(env_id)` against the vmap engine.

    Returns the fused pool's carry after the chunk."""
    import jax
    import numpy as np

    from repro import make_vec
    from repro.core.spaces import sample_batch

    fused_backend = "auto" if mesh is None else "pallas"
    pool = make_vec(env_id, num_envs, backend=fused_backend, mesh=mesh,
                    unroll=unroll)
    assert pool.backend == "pallas", (env_id, pool.backend)
    ref_pool = make_vec(env_id, num_envs, backend="vmap", mesh=mesh,
                        unroll=unroll)
    key = jax.random.PRNGKey(seed)
    acts = jax.jit(jax.vmap(
        lambda k: sample_batch(pool.action_space, k, num_envs)))(
            jax.random.split(jax.random.fold_in(key, 1), unroll))

    h, ref = pool.xla(), ref_pool.xla()
    carry = jax.jit(h.init)(key)
    compiled = jax.jit(h.step_many).lower(carry, acts, key).compile()
    has_kernel = KERNEL_MARK in compiled.as_text()
    say(f"  engine={pool.backend} batch={num_envs} unroll={unroll} "
        f"tpu_custom_call={has_kernel}")
    assert has_kernel, f"{env_id}: backend='auto' compiled no Pallas kernel"
    new_carry, out = compiled(carry, acts, key)
    jax.block_until_ready((new_carry, out))
    say(f"  shapes: {shapes(out)}")
    say(f"  device bytes: {device_bytes(compiled)}")
    assert_finite((new_carry, out), f"{env_id} pallas")

    ref_carry, ref_out = jax.jit(ref.step_many)(jax.jit(ref.init)(key), acts,
                                                key)
    worst = assert_match((ref_carry.env_state, ref_out),
                         (new_carry.env_state, out), f"{env_id} pallas~vmap")
    say(f"  matches vmap over {unroll} steps: max float diff {worst!r} "
        f"(rtol=atol={TOL['rtol']})")
    del ref_carry, ref_out
    n_done = int(np.asarray(out.done).sum())
    say(f"  episodes ended in the chunk: {n_done}")
    return new_carry


def raster_phase(frames: int = 1024, segments: int = 6) -> None:
    """The rasteriser kernel against its jnp reference on the chip (the
    vmap engine renders through the same kernel, so the pool phases do
    not compare the two)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.raster import rasterize

    with Phase("raster:pallas~jnp"):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        ends = jax.random.uniform(k1, (frames, segments, 4))
        radius = jax.random.uniform(k2, (frames, segments, 1), maxval=0.05)
        segs = jnp.concatenate([ends, radius], axis=-1)
        intens = jax.random.uniform(k3, (frames, segments))
        got = rasterize(segs, intens, 84, 84, backend="pallas")
        want = rasterize(segs, intens, 84, 84, backend="jnp")
        jax.block_until_ready((got, want))
        say(f"  shapes: frames{tuple(got.shape)}:{got.dtype} "
            f"from segments{tuple(segs.shape)}")
        say(f"  device bytes: {device_bytes()}")
        worst = assert_match(want, got, "raster")
        say(f"  matches jnp reference: max float diff {worst!r}")


def dqn_reference_check(cfg, state, apply_fn, n: int) -> None:
    """One more fused chunk of `n` train steps from `state`, judged by the
    benchmark's plain reference of the `dqn-cartpole-v1` configuration."""
    import importlib.util
    import pathlib

    import jax
    import jax.numpy as jnp

    from repro.core import make
    from repro.rl import dqn
    from repro.train.fused import fused_train_chunk

    bench = pathlib.Path(__file__).resolve().parent / "bench"
    spec = importlib.util.spec_from_file_location(
        "dqn_cartpole_reference", bench / "reference" / "dqn_cartpole.py")
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    config = json.loads((bench / "configs" / "dqn-cartpole-v1.json")
                        .read_text())
    bound = ref.bind(ref.hparams(config, cfg.num_envs))
    run_chunk = fused_train_chunk(
        dqn.make_train_step(make("CartPole-v1"), apply_fn, cfg))
    before = jax.tree.map(jnp.copy, state)
    after, metrics = run_chunk(state, n)
    steps = jnp.arange(n, dtype=jnp.int32)
    gap, bad = jax.jit(bound.check)(before, steps, metrics, after)
    limits = config["limits"]
    say(f"  one more chunk against the reference: gap {float(gap)!r} "
        f"(limit {limits['gap']}), mismatches {int(bad)} "
        f"(limit {limits['mismatches']})")
    assert float(gap) <= limits["gap"] and int(bad) <= limits["mismatches"], \
        "fused DQN drifted from the float32 reference"


def train_phases() -> None:
    import dataclasses

    import jax

    from repro.configs.cairl_dqn import PAPER_TABLE_I
    from repro.core import make
    from repro.rl import dqn, ppo
    from repro.train.fused import lower_train_chunk

    with Phase("train:dqn-fused"):
        cfg = dataclasses.replace(PAPER_TABLE_I, num_envs=256,
                                  env_backend="pallas")
        steps, chunk = 300, 100
        lowered, carry = lower_train_chunk("dqn", "CartPole-v1", cfg, chunk)
        has_kernel = KERNEL_MARK in lowered.compile().as_text()
        say(f"  CartPole-v1 envs={cfg.num_envs} units={cfg.units} "
            f"replay={cfg.memory_size} steps={steps} chunk={chunk} "
            f"tpu_custom_call={has_kernel}")
        assert has_kernel, "fused DQN with env_backend='pallas' has no kernel"
        state, apply_fn, metrics = dqn.train_compiled(
            make("CartPole-v1"), cfg, steps, jax.random.PRNGKey(0),
            chunk=chunk, fused=True)
        jax.block_until_ready((state, metrics))
        say(f"  shapes: replay.obs{tuple(state.replay.obs.shape)} "
            f"metrics {shapes(metrics)}")
        say(f"  device bytes: {device_bytes()}")
        assert_finite(metrics, "dqn metrics")
        say(f"  final loss {float(metrics['loss'][-1])!r} "
            f"return {float(metrics['return'][-1])!r}")
        dqn_reference_check(cfg, state, apply_fn, chunk)

    with Phase("train:ppo-fused"):
        cfg = ppo.PPOConfig()
        updates, chunk = 6, 2
        say(f"  CartPole-v1 envs={cfg.num_envs} rollout={cfg.rollout_len} "
            f"units={cfg.units} updates={updates} chunk={chunk}")
        state, metrics = ppo.train(make("CartPole-v1"), cfg, updates,
                                   jax.random.PRNGKey(0), fused=True,
                                   chunk=chunk)
        jax.block_until_ready((state, metrics))
        say(f"  shapes: metrics {shapes(metrics)}")
        say(f"  device bytes: {device_bytes()}")
        assert_finite(metrics, "ppo metrics")


def async_phase() -> None:
    import jax
    import numpy as np

    from repro import make_vec
    from repro.core.spaces import sample_batch

    with Phase("async:CartPole-v1"):
        slots, rounds = 256, 4
        pool = make_vec("CartPole-v1", slots, backend="async")
        pool.reset(seed=0)
        for r in range(rounds):
            # every other round only the even slots send: masked lanes
            ids = np.arange(slots) if r % 2 == 0 else np.arange(0, slots, 2)
            acts = np.asarray(sample_batch(pool.action_space,
                                           jax.random.PRNGKey(r), slots))
            pool.send(acts[ids], ids)
            obs, rew, done, _, got = pool.recv()
            jax.block_until_ready(obs)
            assert np.array_equal(np.asarray(got), ids), (r, got)
            assert obs.shape == (len(ids), 4), obs.shape
            assert_finite((obs, rew), f"async round {r}")
        say(f"  slots={slots} rounds={rounds} last obs{tuple(obs.shape)}")
        say(f"  device bytes: {device_bytes()}")


def sharded_phase(chips: int) -> None:
    import jax

    from repro.pool import default_pool_mesh

    env_id, num_envs, unroll = SHARDED_PHASE
    with Phase(f"sharded:{env_id}x{chips}"):
        mesh = default_pool_mesh(chips)
        carry = pool_phase(env_id, num_envs, unroll, mesh=mesh)
        for p, leaf in jax.tree_util.tree_leaves_with_path(carry.env_state):
            devs = {s.device for s in leaf.addressable_shards}
            rows = {s.data.shape[0] for s in leaf.addressable_shards}
            assert len(devs) == chips and rows == {num_envs // chips}, \
                (jax.tree_util.keystr(p), devs, rows)
        say(f"  carry spread over {chips} devices, "
            f"{num_envs // chips} envs each: "
            f"{sorted(str(d) for d in carry.env_state.key.sharding.device_set)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded-pool phase on 4 chips")
    args = ap.parse_args(argv)

    import jax

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
    say(f"device {dev.platform} {dev.device_kind} count={len(devices)} "
        f"jax={jax.__version__} compile cache={cache_dir}")

    if args.chips == 4:
        sharded_phase(args.chips)
    else:
        for env_id, num_envs, unroll in POOL_PHASES:
            with Phase(f"pool:{env_id}"):
                pool_phase(env_id, num_envs, unroll)
        raster_phase()
        train_phases()
        async_phase()

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
