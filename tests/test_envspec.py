"""EnvSpec pipeline + derived-layout coverage (the api_redesign contract).

Three groups:

  - layout derivation: for every fused base env, the auto-derived
    `FusedSpec` must reproduce the hand-written row layout that
    kernels/envstep/specs.py used to carry as per-env field tables
    (`_LEGACY_LAYOUT` below is that table, captured verbatim from the old
    code before deletion), and flatten/unflatten must be exact inverses
    including dtypes.
  - golden traces through `make_vec`: the 32-step checksums committed under
    tests/golden/ must be *bit-identical* through the new frontend's vmap
    path, and within golden tolerance through backend="auto".
  - registry API: `register_family` id generation, the legacy
    `register(name, factory)` shim round-trip, and the helpful
    unknown-kwargs error from `make()`.
"""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_leaves_match

from repro.core import (EnvSpec, declared_pipeline, make, pipeline, register,
                        registered, spec, spec_of)
from repro.core.registry import _REGISTRY
from repro.core.spaces import sample_batch
from repro.core.wrappers import TimeLimit, Vec
from repro.envs.classic import CartPole
from repro.kernels.envstep import spec_for
from repro.kernels.envstep.specs import derive_layout

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"

#: the hand-written layout table the old specs.py carried, captured from the
#: per-env `FusedSpec(name, state_size, obs_size, ...)` rows (plus the row
#: order the dynamics index) before the table was deleted. The derived
#: layout must keep reproducing it — bit-compatibility of every fused
#: kernel depends on the row order.
_LEGACY_LAYOUT = {
    # id of a registry entry whose core is the env: (S, O, obs_is_state,
    #                                               row order of fields)
    "CartPole-raw": (4, 4, True, ("x", "x_dot", "theta", "theta_dot")),
    "MountainCar-raw": (2, 2, True, ("position", "velocity")),
    "Pendulum-raw": (2, 3, False, ("theta", "theta_dot")),
    "Acrobot-raw": (4, 6, False, ("theta1", "theta2", "dtheta1", "dtheta2")),
    "LightsOut-raw": (26, 25, False, ("board", "t")),
    "Pong-raw": (6, 6, True, ("ball_x", "ball_y", "ball_vx", "ball_vy",
                              "player_y", "opp_y")),
    "Breakout-raw": (29, 29, True, ("ball_x", "ball_y", "ball_vx", "ball_vy",
                                    "paddle_x", "bricks")),
    "FrozenLake-raw": (17, 16, False, ("pos", "holes")),
    "CliffWalk-raw": (49, 48, False, ("pos", "cliff")),
    "Maze-raw": (66, 64, False, ("pos", "goal", "walls")),
    "Snake-raw": (76, 36, False, ("head", "food", "length", "eaten",
                                  "ages", "prio")),
}


@pytest.mark.parametrize("name", sorted(_LEGACY_LAYOUT))
def test_derived_layout_matches_legacy_table(name):
    """Auto-derived FusedSpec == the deleted hand-written layout, row for row."""
    s, o, obs_is_state, order = _LEGACY_LAYOUT[name]
    env = make(name)
    fs = spec_for(env)
    assert fs is not None, name
    assert (fs.state_size, fs.obs_size, fs.obs_is_state) == (s, o, obs_is_state)
    # Row order: flatten a batched reset state and check each field lands in
    # the block the legacy layout assigned it.
    venv = Vec(env, 3)
    state, _ = venv.reset(jax.random.PRNGKey(0))
    rows = fs.flatten(state)
    assert rows.shape == (s, 3) and rows.dtype == jnp.float32
    offset = 0
    for field in order:
        leaf = np.asarray(getattr(state, field), np.float32)
        block = leaf.reshape(3, -1).T          # (size, B), row-major
        np.testing.assert_array_equal(
            np.asarray(rows[offset:offset + block.shape[0]]), block,
            err_msg=f"{name}.{field} rows")
        offset += block.shape[0]
    assert offset == s


@pytest.mark.parametrize("name", sorted(_LEGACY_LAYOUT))
def test_flatten_unflatten_round_trip(name):
    """unflatten(flatten(state)) == state exactly, dtypes included."""
    env = make(name)
    fs = spec_for(env)
    venv = Vec(env, 4)
    state, _ = venv.reset(jax.random.PRNGKey(1))
    back = fs.unflatten(fs.flatten(state))
    assert type(back) is type(state)
    assert_leaves_match(state, back, f"{name} roundtrip")


def test_derive_layout_rejects_bad_field_order():
    with pytest.raises(ValueError, match="field_order"):
        derive_layout(CartPole(), field_order=("x", "x_dot"))


# -- golden traces through the make_vec frontend ------------------------------

def _golden_params():
    out = []
    for name in registered():
        marks = [pytest.mark.slow] if spec(name).pixels else []
        out.append(pytest.param(name, marks=marks))
    return out


def _pool_trace(name: str, backend: str):
    """test_golden.trace, but driven through `make_vec(...).xla()`."""
    from repro.pool import make_vec

    want = json.loads((GOLDEN_DIR / f"{name}.json").read_text())
    batch, steps = want["batch"], want["steps"]
    env = make(name)
    handle = make_vec(name, batch, backend=backend).xla()
    # vmap runs op by op, as the golden generator did, so its sums match bit
    # for bit. Other engines are jitted once: called eagerly, the fused step
    # would trace and compile its reset scan again at every step.
    step = handle.step if backend == "vmap" else jax.jit(handle.step)
    key = jax.random.PRNGKey(sum(map(ord, name)))
    ps = handle.init(key)
    rows = []
    for t in range(steps):
        a = sample_batch(env.action_space, jax.random.fold_in(key, 1000 + t),
                         batch)
        ps, out = step(ps, a, jax.random.fold_in(key, t))
        rows.append([float(np.asarray(out.obs, np.float64).sum()),
                     float(np.asarray(out.reward, np.float64).sum()),
                     int(np.asarray(out.done).sum())])
    return want, rows


@pytest.mark.parametrize("name", _golden_params())
def test_golden_bit_identical_through_make_vec(name):
    """The committed checksums hold *bit for bit* through the new frontend:
    `make_vec(id, B, backend="vmap").xla()` is the same computation the
    golden generator ran, so equality is exact, not allclose."""
    want, rows = _pool_trace(name, "vmap")
    assert rows == want["rows"], f"{name}: make_vec(vmap) trace diverged"


@pytest.mark.slow
@pytest.mark.parametrize("name", _golden_params())
def test_golden_through_auto_backend(name):
    """backend="auto" (fused megastep where supported) reproduces the same
    committed checksums within golden tolerance."""
    want, rows = _pool_trace(name, "auto")
    np.testing.assert_allclose(
        np.asarray(rows, np.float64), np.asarray(want["rows"], np.float64),
        rtol=1e-4, atol=1e-4,
        err_msg=f"{name}: make_vec(auto) drifted from the golden trace")


# -- registry API -------------------------------------------------------------

def test_register_family_generated_ids():
    """One family entry -> the declared -v/-px/-raw trio, with pipelines."""
    s = spec("FrozenLake-v0")
    assert s.transforms == (pipeline.TimeLimit(100),)
    assert s.max_steps == 100 and not s.pixels and "grid" in s.tags
    px = spec("FrozenLake-px")
    assert px.transforms == (pipeline.TimeLimit(100), pipeline.ObsToPixels(),
                             pipeline.FrameStack(4))
    assert px.pixels and "pixels" in px.tags
    raw = spec("FrozenLake-raw")
    assert raw.transforms == () and raw.max_steps is None
    assert "raw" in raw.tags
    arcade = spec("Pong-v0")
    assert arcade.pixels and arcade.max_steps == 1000


def test_third_party_register_round_trips():
    """The legacy `register(name, factory)` shim: an opaque wrapper-stack
    factory still registers, builds, and answers the spec API."""
    name = "ThirdParty-test-v0"
    register(name, lambda **kw: TimeLimit(CartPole(**kw), 7))
    try:
        assert name in registered()
        s = spec(name)
        assert isinstance(s, EnvSpec) and s.transforms == ()
        env = make(name)
        assert env.spec is s and spec_of(env) is s
        assert isinstance(env, TimeLimit) and env.max_steps == 7
        # opaque stacks still walk back through their reconstructible wrappers
        core, transforms = declared_pipeline(env)
        assert isinstance(core, CartPole)
        assert transforms == (pipeline.TimeLimit(7),)
        with pytest.raises(ValueError, match="already registered"):
            register(name, CartPole)
    finally:
        _REGISTRY.pop(name, None)


def test_make_unknown_kwargs_error_is_helpful():
    with pytest.raises(TypeError, match=r"gravity.*CartPole-v1|CartPole-v1.*gravity"):
        make("CartPole-v1", gravity=9.8)
    with pytest.raises(TypeError, match=r"scramble_presses"):
        # the error names what IS accepted
        make("LightsOut-v0", bogus=1)
    # opaque factory: the id is still named even though the TypeError comes
    # from inside the factory
    name = "ThirdParty-test-v1"
    register(name, lambda: CartPole())
    try:
        with pytest.raises(TypeError, match=name.replace("-", "[-]")):
            make(name, whatever=3)
    finally:
        _REGISTRY.pop(name, None)


def test_spec_unknown_id_error():
    with pytest.raises(KeyError, match="Nope-v0"):
        spec("Nope-v0")
