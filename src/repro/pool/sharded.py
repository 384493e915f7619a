"""ShardedEnvPool — the env batch sharded across a device mesh.

Jumanji-style scaling: the batch axis of the pool is laid out over the
mesh's data-parallel axes ("pod", "data" — repro.sharding.rules.data_axes)
with `shard_map`, so each device steps `num_envs / n_shards` envs and no
cross-device communication happens inside the step (env steps are
embarrassingly parallel; collectives only appear if the consumer reduces
across the batch). The API is identical to EnvPool — stateful Gym-style,
`xla()`, and `rollout` all work unchanged, which is what makes the
sharded pool a drop-in in benchmarks/fig4_pool_scaling.py.

RNG: every shard folds the (replicated) step key with its linear shard
index so env streams differ across shards. On a 1-device mesh the fold is
skipped, making ShardedEnvPool bit-identical to EnvPool (the parity
contract tests/test_pool.py pins).
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.env import Env
from repro.core.wrappers import AutoReset, Vec
from repro.pool.envpool import EnvPool, PoolState, PoolStep
from repro.sharding.rules import data_axes


def default_pool_mesh(num_devices: Optional[int] = None) -> Mesh:
    """A 1-axis ("data",) mesh over (the first `num_devices`) local devices."""
    devices = jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    return jax.make_mesh((len(devices),), ("data",), devices=devices)


class ShardedEnvPool(EnvPool):
    """EnvPool with the batch dim sharded over the mesh's data axes."""

    def __init__(self, env: Union[Env, str], num_envs: int,
                 mesh: Optional[Mesh] = None, backend: str = "vmap",
                 unroll: int = 1, **env_kwargs):
        self.mesh = mesh if mesh is not None else default_pool_mesh()
        self.axes: Tuple[str, ...] = (data_axes(self.mesh)
                                      or (self.mesh.axis_names[0],))
        self.n_shards = int(np.prod([self.mesh.shape[a] for a in self.axes]))
        if num_envs % self.n_shards:
            raise ValueError(
                f"num_envs={num_envs} must divide evenly over the "
                f"{self.n_shards}-way data axes {self.axes} of the mesh")
        super().__init__(env, num_envs, backend=backend, unroll=unroll,
                         **env_kwargs)
        self._local = Vec(AutoReset(self.env), self.num_envs // self.n_shards)
        self._bspec = P(self.axes)        # batch dim over the data axes
        self._cspec = P(None, self.axes)  # (K, B, ...) step-chunk arrays

    def _put_carry(self, d):
        """Re-place a (gathered, host) carry snapshot onto THIS pool's mesh:
        batch-leading leaves shard over the data axes, the carry key
        replicates. Snapshots are mesh-agnostic (checkpoint/manager.py), so
        this is the rebuild-shardings leg of the elastic restore path — a
        snapshot taken on a bigger mesh restores here unchanged."""
        batch_sh = NamedSharding(self.mesh, self._bspec)
        repl_sh = NamedSharding(self.mesh, P())
        return {
            "env_state": jax.tree.map(
                lambda x: jax.device_put(np.asarray(x), batch_sh),
                d["env_state"]),
            "obs": jax.device_put(np.asarray(d["obs"]), batch_sh),
            "key": jax.device_put(np.asarray(d["key"]), repl_sh),
        }

    def _shard_key(self, key: jax.Array) -> jax.Array:
        """Per-shard RNG stream; identity on a 1-device mesh (exact parity)."""
        if self.n_shards == 1:
            return key
        idx = jnp.asarray(0, jnp.int32)
        for a in self.axes:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return jax.random.fold_in(key, idx)

    # -- XLA-resident pure API, shard_mapped ----------------------------------
    def _xla_init(self, key: jax.Array) -> PoolState:
        def local_reset(k):
            return self._local.reset(self._shard_key(k))

        state, obs = jax.shard_map(
            local_reset, mesh=self.mesh, in_specs=P(),
            out_specs=(self._bspec, self._bspec), check_vma=False,
        )(key)
        return PoolState(state, obs, jax.random.fold_in(key, 0x57EB))

    def _step_many_core(self, env_state, actions, key, venv=None):
        """The K-step block, shard_mapped: each shard runs the fused megastep
        kernel (or the scanned vmap step) on its `num_envs / n_shards` slice
        of the batch — one kernel launch per shard per chunk, still with no
        collectives in the body."""
        def local_many(state, a, k):
            state, (obs, rew, done, info) = EnvPool._step_many_core(
                self, state, a, self._shard_key(k), venv=self._local)
            return state, obs, rew, done, info

        state, obs, rew, done, info = jax.shard_map(
            local_many, mesh=self.mesh,
            in_specs=(self._bspec, self._cspec, P()),
            out_specs=(self._bspec, self._cspec, self._cspec, self._cspec,
                       self._cspec),
            check_vma=False,
        )(env_state, actions, key)
        return state, (obs, rew, done, info)

    def _xla_step(self, carry: PoolState, actions: jax.Array,
                  key: Optional[jax.Array] = None) -> Tuple[PoolState, PoolStep]:
        if self._fused:  # route through the shard_mapped megastep block
            return EnvPool._xla_step(self, carry, actions, key)
        if key is None:
            next_key, key = jax.random.split(carry.key)
        else:
            next_key = carry.key

        def local_step(state, a, k):
            ts = self._local.step(state, a, self._shard_key(k))
            return ts.state, ts.obs, ts.reward, ts.done, ts.info

        state, obs, reward, done, info = jax.shard_map(
            local_step, mesh=self.mesh,
            in_specs=(self._bspec, self._bspec, P()),
            out_specs=(self._bspec, self._bspec, self._bspec, self._bspec,
                       self._bspec),
            check_vma=False,
        )(carry.env_state, actions, key)
        return (PoolState(state, obs, next_key),
                PoolStep(obs, reward, done, info))
