"""Fused DQN training consumer: the learner and the env pool in one program.

The carry (network params, target, Adam state, replay ring, pool state, key
chain and returns) is built on the device from the seed by
`repro.rl.dqn.dqn_init`; the timed program is
`repro.train.fused.fused_train_chunk(dqn.make_train_step(...))` at
`train_steps_per_chunk` steps, the carry donated: what
`dqn.train_compiled(..., fused=True)` dispatches. Each train step acts on
every lane epsilon-greedily, steps the pool once, stores the transitions
and takes one learner update. The window is a closed loop with one chunk in
flight: dispatch, block until the chunk's metrics are ready, repeat.

Traffic keys: `num_envs`, `train_steps_per_chunk`, and `check_chunks`:
that many chunks, drawn from the seed among the first `check_range`
(default the rollout consumer's `CHECK_RANGE`) of the window, are judged
against the configuration's reference. After the window one more chunk of
the same compiled program runs from the window's last carry with the time
counters of lanes drawn from the seed set 1 to `train_steps_per_chunk`
steps short of the time limit, so that the judged chunks hold truncations,
which are stored as not terminal and bootstrap through their terminal obs.
"""
from __future__ import annotations

import importlib.util
import pathlib
import re
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_consumers_rollout_for_dqn",
    pathlib.Path(__file__).resolve().parent / "rollout.py")
rollout = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(rollout)
#: chunks run in set-up after compiling, before the window: past
#: `learn_start` and past the ring's first wrap at the cell's sizes
WARM_CHUNKS = rollout.WARM_CHUNKS
seed_parts, key_from_parts = rollout.seed_parts, rollout.key_from_parts
#: a matmul of a compiled program, and what it carries at HIGHEST precision
_MATMUL = re.compile(r"= \S+ (?:dot|convolution)\(")
HIGHEST = "operand_precision={highest,highest}"


def check_float32_matmuls(hlo_text: str) -> None:
    """The configuration states a float32 learner; at DEFAULT precision a
    TPU computes a float32 matmul in one bfloat16 pass, so a program whose
    matmuls do not all ask for HIGHEST cannot run it."""
    low = [line.strip()[:120] for line in hlo_text.splitlines()
           if _MATMUL.search(line) and HIGHEST not in line]
    if low:
        raise RuntimeError(f"{len(low)} matmuls of the train step are not "
                           f"at float32 (HIGHEST) precision, e.g. {low[0]}")


def dqn_config(config: Dict, traffic: Dict):
    """The program's `DQNConfig` from the configuration's `dqn` group."""
    from repro.rl.dqn import DQNConfig
    from repro.train.optim import Adam

    opt = config["optimizer"]
    adam = Adam()
    if (opt["b1"], opt["b2"], opt["eps"]) != (adam.b1, adam.b2, adam.eps):
        raise ValueError(f"the program's Adam is ({adam.b1}, {adam.b2}, "
                         f"{adam.eps}), the configuration's {opt}")
    d = dict(config["dqn"], units=tuple(config["dqn"]["units"]))
    return DQNConfig(**d, num_envs=int(traffic["num_envs"]),
                     env_backend=config["backend"])


class Cell:
    """One cell of this consumer: `config` and `traffic` are the cell's
    files, `ref` the configuration's reference module."""

    def __init__(self, config: Dict, traffic: Dict, ref, seed: int, devices):
        from repro import make_vec
        from repro.core import make
        from repro.rl import dqn
        from repro.train.fused import fused_train_chunk

        self.config, self.traffic = config, traffic
        self.num_envs = int(traffic["num_envs"])
        self.n = int(traffic["train_steps_per_chunk"])
        self.steps_per_chunk = self.n * self.num_envs
        self.ref = ref.bind(ref.hparams(config, self.num_envs))
        cfg = dqn_config(config, traffic)
        env = make(config["env_id"])
        engine = make_vec(env, self.num_envs, backend=cfg.env_backend).backend
        if engine != config["engine"]:
            raise RuntimeError(f"{config['env_id']}: backend "
                               f"{cfg.env_backend!r} resolved to {engine!r}, "
                               f"not {config['engine']!r}")

        apply_fns = []

        def build(lo, hi):
            """The training carry and its key, from the seed, in one program
            on the device."""
            key = key_from_parts(lo, hi)
            state, apply_fn = dqn.dqn_init(env, cfg, key)
            apply_fns.append(apply_fn)
            return state, key

        self.state, self.init_key = jax.jit(build)(*seed_parts(seed))
        step_fn = dqn.make_train_step(env, apply_fns[0], cfg)
        self._chunk = fused_train_chunk(step_fn).lower(
            self.state, self.n).compile()
        check_float32_matmuls(self._chunk.as_text())
        self._copy = jax.jit(lambda s: jax.tree.map(jnp.copy, s)).lower(
            self.state).compile()
        self.init_copy = self._copy(self.state)
        self.steps = jnp.arange(self.n, dtype=jnp.int32)
        self.rng = np.random.default_rng(seed)
        self.check_at = set(self.rng.choice(
            int(traffic.get("check_range", rollout.CHECK_RANGE)),
            int(traffic["check_chunks"]), replace=False).tolist())
        self.checked: List = []
        self.limit_chunk = None

    def hlo_texts(self) -> List[str]:
        return [self._chunk.as_text()]

    def warm(self) -> None:
        """Run the cell's own shapes until they are compiled and warm."""
        for _ in range(WARM_CHUNKS):
            self.state, out = self._chunk(self.state)
            jax.block_until_ready(out)
        jax.block_until_ready(self._copy(self.state))

    #: the closed loop, one chunk in flight, as the rollout consumer's
    window = rollout.Cell.window

    def close(self) -> int:
        """After the window: one more chunk of the window's own program
        from its last carry, with the time counters of about half the
        lanes, drawn from the seed, set 1 to `train_steps_per_chunk` steps
        short of the time limit; it is judged with the checked chunks.
        Returns the truncations in that chunk."""
        ref, n = self.ref, self.n
        t = ref.time_counters(self.state)
        r = self.rng.integers(0, 2 * n, t.shape)
        near = np.where(r < n, ref.MAX_STEPS - 1 - r, np.asarray(t))
        state = ref.with_time(self.state, jax.device_put(
            near.astype(t.dtype), t.sharding))
        del self.state
        before = self._copy(state)
        after, out = self._chunk(state)
        self.limit_chunk = (before, out, after)
        return int(jax.jit(ref.truncations)(before, self.steps, after))

    def layer_context(self) -> Dict:
        """What the per-layer readers need besides the trace."""
        return {"train_steps_per_chunk": self.n}

    def checked_chunks(self):
        """(carry in, step offsets, metrics, carry out) of each checked
        chunk."""
        if len(self.checked) < int(self.traffic["check_chunks"]):
            raise RuntimeError(
                f"the window ended before chunk {max(self.check_at)}; the "
                "checked chunks must lie inside it")
        if self.limit_chunk is None:
            raise RuntimeError("close() runs the chunk near the time limit")
        for before, out, after in self.checked + [self.limit_chunk]:
            yield before, self.steps, out, after

    def check(self):
        """The numbers compared, each with its limit, and how many of the
        judged carries and chunks failed; after `close`."""
        ref, limits = self.ref, self.config["limits"]
        g, b = jax.jit(ref.check_init)(self.init_copy, self.init_key)
        gaps, bads = [float(g)], [int(b)]
        judge = jax.jit(ref.check)
        for chunk in self.checked_chunks():
            g, b = judge(*chunk)
            gaps.append(float(g))
            bads.append(int(b))
        failed = sum(g > limits["gap"] or b > limits["mismatches"]
                     for g, b in zip(gaps, bads))
        return {"gap": {"value": max(gaps), "limit": limits["gap"]},
                "mismatches": {"value": sum(bads),
                               "limit": limits["mismatches"]}}, failed
