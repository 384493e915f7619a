"""Arcade pixel-game suite: dynamics, baselines, and fused-engine parity.

Mirrors tests/test_envstep_fused.py for the pixel workload class: for
`Pong-v0` / `Breakout-v0` (FrameStack(ObsToPixels(TimeLimit(game)))) and the
`-raw` state-vector variants, the fused megastep path — game logic in the
kernel, frames rasterised per-chunk outside it — must reproduce the
scan-of-vmap-step trajectory (exact for int/bool fields, <=1e-5 floats),
including auto-reset boundaries and the frame-stack ring. Pixel rollouts
must stay device-resident (zero host transfers in the compiled HLO) and be
deterministic in the key.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make
from repro.core.env import supports_fused_step
from repro.core.spaces import sample_batch
from repro.core.wrappers import AutoReset, FrameStack, ObsToPixels, TimeLimit, Vec
from repro.envs.arcade import Breakout, Pong
from repro.envs.arcade.breakout import BreakoutState
from repro.envs.arcade.pong import PongState
from repro.envs.baseline_python.arcade import BreakoutPy, PongPy
from repro.kernels.envstep import fused_step
from repro.launch.hlo_analysis import host_transfer_ops
from repro.pool import EnvPool, ShardedEnvPool, default_pool_mesh

ARCADE_IDS = ["Pong-v0", "Breakout-v0", "Pong-raw", "Breakout-raw"]
BACKENDS = ("jnp", "pallas_interpret")


# -- dynamics vs the interpreted ports (test_envs.py pattern) ----------------

def test_pong_matches_python():
    actions = [0, 2, 1, 2, 2, 0, 1, 2, 0, 1, 2, 2, 1, 0, 2]
    py = PongPy()
    py.reset()
    py.ball_x, py.ball_y = 0.5, 0.4
    py.ball_vx, py.ball_vy = 0.035, 0.013
    py.player_y, py.opp_y = 0.45, 0.55
    env = Pong()
    state = PongState(*(jnp.asarray(v, jnp.float32)
                        for v in (0.5, 0.4, 0.035, 0.013, 0.45, 0.55)))
    for a in actions:
        po, pr, pd, _ = py.step(a)
        ts = env.step(state, jnp.asarray(a), jax.random.PRNGKey(0))
        state = ts.state
        np.testing.assert_allclose(np.asarray(ts.obs), np.asarray(po),
                                   rtol=1e-5, atol=1e-6)
        assert pd == bool(ts.done) and abs(pr - float(ts.reward)) < 1e-6


def test_breakout_matches_python_and_breaks_bricks():
    actions = [1, 1, 1, 0, 2, 1, 1, 1, 0, 2, 1, 1]
    py = BreakoutPy()
    py.reset()
    py.ball_x, py.ball_y = 0.31, 0.505   # off the brick-boundary lattice
    py.ball_vx, py.ball_vy = 0.022, -0.03
    py.paddle_x = 0.4
    py.bricks = [[1] * 6 for _ in range(4)]
    env = Breakout()
    state = BreakoutState(*(jnp.asarray(v, jnp.float32)
                            for v in (0.31, 0.505, 0.022, -0.03, 0.4)),
                          jnp.ones((4, 6), jnp.int32))
    broke = 0.0
    for a in actions:
        po, pr, pd, _ = py.step(a)
        ts = env.step(state, jnp.asarray(a), jax.random.PRNGKey(0))
        state = ts.state
        np.testing.assert_allclose(np.asarray(ts.obs), np.asarray(po),
                                   rtol=1e-5, atol=1e-6)
        assert pd == bool(ts.done) and abs(pr - float(ts.reward)) < 1e-6
        broke += pr
    assert broke >= 1.0  # the upward serve reached the brick grid


def test_pong_scores_and_terminates():
    env = Pong()
    # ball one step from passing the agent, paddle far away
    state = PongState(*(jnp.asarray(v, jnp.float32)
                        for v in (0.98, 0.2, 0.035, 0.0, 0.8, 0.5)))
    ts = env.step(state, jnp.asarray(1), jax.random.PRNGKey(0))
    assert bool(ts.done) and float(ts.reward) == -1.0


def test_breakout_clear_bonus():
    env = Breakout()
    bricks = jnp.zeros((4, 6), jnp.int32).at[3, 2].set(1)  # one brick left
    # ball inside the last brick's cell next step: x≈0.41 (col 2), y->0.295
    state = BreakoutState(*(jnp.asarray(v, jnp.float32)
                            for v in (0.41, 0.325, 0.0, -0.03, 0.5)), bricks)
    ts = env.step(state, jnp.asarray(1), jax.random.PRNGKey(0))
    assert bool(ts.done) and float(ts.reward) == 6.0  # +1 brick, +5 clear


def test_pixel_obs_pipeline_shapes():
    env = make("Pong-v0")
    assert env.observation_space.shape == (4, 84, 84)
    state, obs = env.reset(jax.random.PRNGKey(0))
    assert obs.shape == (4, 84, 84)
    ts = env.step(state, jnp.asarray(2), jax.random.PRNGKey(1))
    assert ts.obs.shape == (4, 84, 84)
    # the newest frame enters at the end of the ring and pixels move
    assert not np.allclose(np.asarray(ts.obs[3]), np.asarray(obs[3]))
    assert "truncated" in ts.info


def test_supports_fused_step_arcade_contract():
    for name in ARCADE_IDS:
        assert supports_fused_step(make(name)), name
    # FrameStack over a non-pixel env is NOT modelled by the fused engine
    assert not supports_fused_step(FrameStack(make("CartPole-v1"), 4))


# -- fused vs vmap parity (pixel pipeline included) ---------------------------

def _vmap_reference(env, num_envs, key, actions):
    venv = Vec(AutoReset(env), num_envs)
    state0, _ = venv.reset(key)
    state, outs = state0, []
    for t in range(actions.shape[0]):
        ts = venv.step(state, actions[t], jax.random.fold_in(key, t))
        state = ts.state
        outs.append(ts)
    return state0, state, outs


def _check_parity(env, num_envs, key, actions, backend):
    st0, st_ref, outs = _vmap_reference(env, num_envs, key, actions)
    st_f, ts = fused_step(env, st0, actions, backend=backend)
    stack = lambda f: jnp.stack([f(o) for o in outs])
    np.testing.assert_allclose(np.asarray(ts.obs),
                               np.asarray(stack(lambda o: o.obs)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ts.reward),
                               np.asarray(stack(lambda o: o.reward)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ts.done),
                                  np.asarray(stack(lambda o: o.done)))
    np.testing.assert_allclose(
        np.asarray(ts.info["terminal_obs"]),
        np.asarray(stack(lambda o: o.info["terminal_obs"])),
        rtol=1e-5, atol=1e-6)
    if "truncated" in outs[0].info:
        np.testing.assert_array_equal(
            np.asarray(ts.info["truncated"]),
            np.asarray(stack(lambda o: o.info["truncated"])))
    for a, b in zip(jax.tree.leaves(st_ref), jax.tree.leaves(st_f)):
        assert a.dtype == b.dtype and a.shape == b.shape
        if np.issubdtype(np.asarray(a).dtype, np.integer) or \
                np.asarray(a).dtype == np.uint32:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
    return stack(lambda o: o.done)


@pytest.mark.slow
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", ARCADE_IDS)
def test_arcade_fused_matches_vmap(name, backend):
    env = make(name)
    num_envs, k = 4, 10
    key = jax.random.PRNGKey(sum(map(ord, name)))
    actions = jnp.stack([
        sample_batch(env.action_space, jax.random.fold_in(key, 100 + t),
                     num_envs) for t in range(k)])
    _check_parity(env, num_envs, key, actions, backend)


@pytest.mark.slow
@pytest.mark.parametrize("name", ["Pong-v0", "Breakout-v0"])
def test_arcade_fused_autoreset_boundary(name):
    """Under 'stay' the ball drops / rallies end well inside K: the pixel
    auto-reset re-entry (fresh frames + frame-stack ring refill) fires."""
    env = make(name)
    k, num_envs = 40, 4
    actions = jnp.ones((k, num_envs), jnp.int32)
    # The key fixes the serve directions under JAX's default threefry
    # stream; with it both games end at least num_envs rallies inside K.
    done = _check_parity(env, num_envs, jax.random.PRNGKey(4), actions, "jnp")
    assert int(np.asarray(done).sum()) >= num_envs


@pytest.mark.slow
def test_arcade_timelimit_truncation_fused():
    """A short pixel TimeLimit truncates inside K: counter + ring both reset."""
    env = FrameStack(ObsToPixels(TimeLimit(Pong(), 6)), 3)
    k, num_envs = 14, 3
    actions = jnp.zeros((k, num_envs), jnp.int32)
    done = _check_parity(env, num_envs, jax.random.PRNGKey(4), actions, "jnp")
    assert int(np.asarray(done).sum()) >= 2 * num_envs


# -- pools ---------------------------------------------------------------------

def test_arcade_pool_pallas_interpret_acceptance():
    """Acceptance: both arcade ids run through
    EnvPool(backend="pallas_interpret", unroll=8) — Pallas megastep kernel
    AND Pallas rasteriser, both in interpret mode."""
    for name in ("Pong-v0", "Breakout-v0"):
        pool = EnvPool(name, 4, backend="pallas_interpret", unroll=8)
        obs = pool.reset(seed=0)
        assert obs.shape == (4, 4, 84, 84)
        obs, rew, done, info = pool.step(pool.sample_actions(0))
        assert obs.shape == (4, 4, 84, 84)
        assert "truncated" in info and "terminal_obs" in info
        rew_f, eps_f, _ = pool.rollout(16, jax.random.PRNGKey(1))
        assert np.isfinite(np.asarray(rew_f)).all()


@pytest.mark.slow
def test_arcade_pool_fused_rollout_matches_vmap():
    key = jax.random.PRNGKey(7)
    rew_v, eps_v, _ = EnvPool("Breakout-v0", 4).rollout(30, key)
    rew_f, eps_f, _ = EnvPool("Breakout-v0", 4, backend="jnp",
                              unroll=8).rollout(30, key)  # 30 = 3*8 + 6
    np.testing.assert_allclose(np.asarray(rew_v), np.asarray(rew_f),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(eps_v), np.asarray(eps_f))
    assert int(np.asarray(eps_v).sum()) > 0  # episodes crossed chunk seams


@pytest.mark.slow
def test_arcade_sharded_matches_unsharded_on_one_device_mesh():
    key = jax.random.PRNGKey(5)
    sharded = ShardedEnvPool("Pong-v0", 4, mesh=default_pool_mesh(1),
                             backend="jnp", unroll=8)
    plain = EnvPool("Pong-v0", 4)
    rew_s, eps_s, _ = sharded.rollout(20, key)
    rew_u, eps_u, _ = plain.rollout(20, key)
    np.testing.assert_allclose(np.asarray(rew_s), np.asarray(rew_u),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(eps_s), np.asarray(eps_u))


def test_arcade_pixel_rollout_is_device_resident():
    """Acceptance: zero host transfers in the compiled fused PIXEL rollout —
    rendering included."""
    pool = EnvPool("Pong-v0", 8, backend="jnp", unroll=8)
    hlo = pool.rollout_lowered(16).compile().as_text()
    assert host_transfer_ops(hlo) == []


def test_arcade_pixel_rollout_deterministic():
    """Same key ⇒ same pixel rollout, including the final observation."""
    key = jax.random.PRNGKey(3)
    p1 = EnvPool("Breakout-v0", 3, backend="jnp", unroll=4)
    p2 = EnvPool("Breakout-v0", 3, backend="jnp", unroll=4)
    r1, e1, _ = p1.rollout(12, key)
    r2, e2, _ = p2.rollout(12, key)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    o1, o2 = p1.reset(seed=9), p2.reset(seed=9)
    a = p1.sample_actions(0)
    np.testing.assert_array_equal(np.asarray(p1.step(a)[0]),
                                  np.asarray(p2.step(a)[0]))


# -- learning ------------------------------------------------------------------

@pytest.mark.slow
def test_dqn_cnn_trains_on_pong_pixels():
    """The end-to-end §IV-C claim: pixel obs feed DQN's CNN on device, on
    both step engines, with matching training curves."""
    import dataclasses

    from repro.rl.dqn import DQNConfig, train_compiled

    env = make("Pong-v0")
    cfg = DQNConfig(network="cnn", num_envs=2, learn_start=8, memory_size=64,
                    batch_size=8)
    key = jax.random.PRNGKey(0)
    _, _, m_v = train_compiled(env, cfg, 10, key)
    _, _, m_f = train_compiled(
        env, dataclasses.replace(cfg, env_backend="jnp"), 10, key)
    assert np.isfinite(np.asarray(m_v["loss"])).all()
    np.testing.assert_allclose(np.asarray(m_v["return"]),
                               np.asarray(m_f["return"]), rtol=1e-4, atol=1e-4)
