"""Rollout consumer: a synchronous actor feeding a learner.

The window drives the benchmark's own jitted chunk program, one dispatch
per chunk with the carry donated: under `bench.policy` it samples uniform
random actions from a key that rides in the carry; under `bench.env_step`
it calls `make_vec(id, B, unroll=K[, mesh]).xla().step_many`. It returns
the chunk's actions and whole `PoolStep` (obs, reward, done, terminal obs,
truncation), as a learner would receive them. The loop is closed with one
chunk in flight: dispatch, block until the trajectory is ready, repeat.

Traffic keys: `num_envs`, `unroll`, `mesh_devices` (1: `EnvPool`; more:
`ShardedEnvPool` over that many chips), `policy` ("uniform"), and
`check_chunks`: that many chunks, drawn from the seed among the first
`check_range` (default `CHECK_RANGE`) of the window, are judged against the
configuration's reference. After the window one more chunk of the same
compiled program runs from the window's last carry with the time counters
of lanes drawn from the seed set to 1 to `unroll` steps short of the time
limit, so that the judged chunks hold truncations and the resets after
them.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

#: chunks run in set-up after compiling, before the window
WARM_CHUNKS = 3
#: the checked chunks lie among the window's first CHECK_RANGE
CHECK_RANGE = 64


def seed_parts(seed: int):
    """Any non-negative whole number (more than 32 bits too) as two int32
    words, the inputs of `key_from_parts`."""
    return (np.int32(seed & 0x7FFFFFFF), np.int32((seed >> 31) & 0x7FFFFFFF))


def key_from_parts(lo, hi) -> jax.Array:
    """The run's key from the two words of its seed."""
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def make_pool(config: Dict, traffic: Dict, devices):
    from repro import make_vec
    from repro.pool import default_pool_mesh

    n = int(traffic.get("mesh_devices", 1))
    mesh = default_pool_mesh(n) if n > 1 else None
    pool = make_vec(config["env_id"], int(traffic["num_envs"]),
                    backend=config["backend"], unroll=int(traffic["unroll"]),
                    mesh=mesh)
    if pool.backend != config["engine"]:
        raise RuntimeError(f"{config['env_id']}: backend "
                           f"{config['backend']!r} resolved to "
                           f"{pool.backend!r}, not {config['engine']!r}")
    return pool


def env_step(pool):
    """The timed path: the pool's K-step block."""
    return pool.xla().step_many


def annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


class Cell:
    """One cell of this consumer: `config` and `traffic` are the cell's
    files, `ref` the configuration's reference module."""

    def __init__(self, config: Dict, traffic: Dict, ref, seed: int, devices):
        if traffic["policy"] != "uniform":
            raise ValueError(f"unknown policy {traffic['policy']!r}")
        self.config, self.traffic, self.devices = config, traffic, devices
        self.ref = ref
        self.num_envs = int(traffic["num_envs"])
        self.unroll = int(traffic["unroll"])
        self.n_shards = int(traffic.get("mesh_devices", 1))
        self.steps_per_chunk = self.num_envs * self.unroll

        self.pool = make_pool(config, traffic, devices)
        n_actions = int(self.pool.action_space.n)
        step_many = env_step(self.pool)
        k, b = self.unroll, self.num_envs

        def chunk(state):
            carry, key = state
            key, sub = jax.random.split(key)
            with jax.named_scope("bench.policy"):
                acts = jax.random.randint(sub, (k, b), 0, n_actions,
                                          dtype=jnp.int32)
            with jax.named_scope("bench.env_step"):
                carry, out = step_many(carry, acts)
            return (carry, key), (acts, out)

        init = self.pool.xla().init

        def build(lo, hi):
            """The pool's carry, the policy key and the init key, from the
            seed, in one program on the device."""
            init_key, policy_key = jax.random.split(key_from_parts(lo, hi))
            return (init(init_key), policy_key), init_key

        self.state, self.init_key = jax.jit(build)(*seed_parts(seed))
        self._chunk = jax.jit(chunk, donate_argnums=0).lower(
            self.state).compile()
        self._copy = jax.jit(lambda s: jax.tree.map(jnp.copy, s)).lower(
            self.state).compile()
        self.init_copy = self._copy(self.state)[0]
        self.rng = np.random.default_rng(seed)
        self.check_at = set(self.rng.choice(
            int(traffic.get("check_range", CHECK_RANGE)),
            int(traffic["check_chunks"]), replace=False).tolist())
        self.checked: List = []
        self.limit_chunk = None
        self.shapes = None

    def hlo_texts(self) -> List[str]:
        return [self._chunk.as_text()]

    def warm(self) -> None:
        """Run the cell's own shapes until they are compiled and warm."""
        for _ in range(WARM_CHUNKS):
            self.state, out = self._chunk(self.state)
            jax.block_until_ready(out)
        jax.block_until_ready(self._copy(self.state))
        self.shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            (self.state[0], out))

    def window(self, seconds: float) -> Dict:
        """The closed loop for `seconds`: dispatch, block, repeat."""
        dispatch_s: List[float] = []
        # (dispatch, ready) of every chunk, on the host clock
        marks: List = []
        chunks = 0
        with annotate("bench.window"):
            t0 = time.perf_counter()
            while True:
                snap = chunks in self.check_at
                if snap:
                    with annotate("bench.snapshot"):
                        before = self._copy(self.state)
                with annotate("bench.dispatch"):
                    d0 = time.perf_counter()
                    self.state, out = self._chunk(self.state)
                    dispatch_s.append(time.perf_counter() - d0)
                with annotate("bench.block"):
                    jax.block_until_ready(out)
                marks.append((d0, time.perf_counter()))
                if snap:
                    with annotate("bench.snapshot"):
                        self.checked.append((before, out,
                                             self._copy(self.state)))
                chunks += 1
                now = time.perf_counter()
                if now - t0 >= seconds:
                    break
        # every transition of every chunk over all the window's time
        steps_per_s = chunks * self.steps_per_chunk / (now - t0)
        return {"chunks": chunks, "seconds": now - t0,
                "dispatch_s": dispatch_s, "chunk_marks": marks,
                "snapshot_chunks": sorted(self.check_at),
                "end_to_end": {"env_steps_per_s": steps_per_s}}

    def close(self) -> int:
        """After the window: one more chunk of the window's own program
        from its last carry, with the time counters of about half the
        lanes, drawn from the seed, set 1 to `unroll` steps short of the
        time limit; it is judged with the checked chunks. Then the pool's
        state is freed. Returns the truncations in that chunk."""
        ref, k = self.ref, self.unroll
        carry, policy_key = self.state
        t = ref.lanes(carry)[1]
        r = self.rng.integers(0, 2 * k, t.shape)
        near = np.where(r < k, ref.MAX_STEPS - 1 - r, np.asarray(t))
        state = (ref.with_time(carry, jax.device_put(
            near.astype(t.dtype), t.sharding)), policy_key)
        del self.state
        before = self._copy(state)
        after, out = self._chunk(state)
        self.limit_chunk = (before, out, after)
        return int(jnp.sum(out[1].info["truncated"]))

    def layer_context(self) -> Dict:
        """What the per-layer readers need besides the trace."""
        from work import frame_bytes, step_interface_bytes

        carry_sds, (acts_sds, out_sds) = self.shapes
        ctx = {"interface_bytes": step_interface_bytes(
            carry_sds, acts_sds, carry_sds, out_sds)}
        if "frame" in self.config:
            h, w = self.config["frame"]
            ctx["frame_bytes"] = frame_bytes(self.steps_per_chunk, h, w,
                                             out_sds.obs.dtype)
        return ctx

    def _one_device(self, tree):
        """`tree` on one device: the reference runs there, whatever the
        pool's mesh."""
        return jax.tree.map(
            lambda x: x if len(x.sharding.device_set) == 1 else
            jax.device_put(np.asarray(x), self.devices[0]), tree)

    def checked_chunks(self):
        """(carry in, actions, output, carry out) of each checked chunk, on
        one device."""
        if len(self.checked) < int(self.traffic["check_chunks"]):
            raise RuntimeError(
                f"the window ended before chunk {max(self.check_at)}; the "
                "checked chunks must lie inside it")
        if self.limit_chunk is None:
            raise RuntimeError("close() runs the chunk near the time limit")
        for before, (acts, out), after in self.checked + [self.limit_chunk]:
            yield self._one_device((before[0], acts, out, after[0]))

    def check(self):
        """The numbers compared, each with its limit, and how many of the
        judged carries and chunks failed; after `close`."""
        ref, limits = self.ref, self.config["limits"]
        g, b = jax.jit(ref.check_init, static_argnums=(2, 3))(
            *self._one_device((self.init_copy, self.init_key)),
            self.num_envs, self.n_shards)
        gaps, bads = [float(g)], [int(b)]
        judge = jax.jit(lambda c, a, o, after: ref.check(c, a, o,
                                                         ref.lanes(after)))
        for chunk in self.checked_chunks():
            g, b = judge(*chunk)
            gaps.append(float(g))
            bads.append(int(b))
        failed = sum(g > limits["gap"] or b > limits["mismatches"]
                     for g, b in zip(gaps, bads))
        return {"gap": {"value": max(gaps), "limit": limits["gap"]},
                "mismatches": {"value": sum(bads),
                               "limit": limits["mismatches"]}}, failed
