"""The main path's Pallas kernels compile for a TPU v5e, at deployment size.

No chip is needed: the TPU compiler is installed, and it compiles for a chip
that is described, not attached. Each test compiles one kernel and asserts
that the compiled program holds it (`tpu_custom_call`): the megastep for
each of the 11 fused families at 4096 envs × 32 steps, and the rasteriser
over 1024 frames of 84×84. What the chip's compiler refuses (a slice it
cannot tile, an op it cannot lower, too much fast memory) fails here.

Only one process at a time may load the TPU compiler's library. So the
topology is described inside a module fixture, never while a module is
imported, and every test of this kind stays in this one file. The
persistent compilation cache is off around these compiles: an entry
written for a described chip cannot be read back without one.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import make
from repro.kernels.envstep.megastep import megastep_pallas
from repro.kernels.envstep.specs import lookup
from repro.kernels.raster.raster import rasterize_pallas

BATCH, UNROLL = 4096, 32
FRAMES, SEGMENTS, HEIGHT, WIDTH = 1024, 6, 84, 84

#: one id per fused family (kernels/envstep/specs.py `_dynamics`)
FUSED_IDS = ("CartPole-v1", "MountainCar-v0", "Pendulum-v1", "Acrobot-v1",
             "LightsOut-v0", "Pong-raw", "Breakout-raw", "FrozenLake-v0",
             "CliffWalk-v0", "Maze-v0", "Snake-v0")


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e 2x2 host, with the compilation cache off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_every_fused_family_is_compiled():
    from repro.kernels.envstep.specs import _dynamics

    families = {type(make(i).unwrapped) for i in FUSED_IDS}
    assert families == set(_dynamics())


@pytest.mark.parametrize("env_id", FUSED_IDS)
def test_megastep_compiles_for_v5e(env_id, one_chip):
    spec, max_steps = lookup(make(env_id))
    rows = spec.state_size + (max_steps is not None)
    text = _compiled_text(
        functools.partial(megastep_pallas, spec.step_rows,
                          max_steps=max_steps),
        (rows, BATCH), (UNROLL, BATCH), (UNROLL, rows, BATCH),
        (UNROLL, spec.obs_size, BATCH), sharding=one_chip)
    assert "tpu_custom_call" in text, env_id


def test_rasteriser_compiles_for_v5e(one_chip):
    text = _compiled_text(
        functools.partial(rasterize_pallas, h=HEIGHT, w=WIDTH),
        (FRAMES, SEGMENTS, 5), (FRAMES, SEGMENTS), sharding=one_chip)
    assert "tpu_custom_call" in text
