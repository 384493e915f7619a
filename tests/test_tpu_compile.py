"""The main path's Pallas kernels compile for a TPU v5e, at deployment size.

No chip is needed: the TPU compiler is installed, and it compiles for a chip
that is described, not attached. Each test compiles one kernel and asserts
that the compiled program holds it (`tpu_custom_call`) under the kernel's
own name, the one a profile's reader (`bench/trace_reduce.kernel_of`)
looks for: the megastep for each of the 11 fused families at 4096 envs ×
32 steps, the rasteriser over 1024 frames of 84×84, and the frame stack at
1024 envs × 8 steps of 4×84×84 frames. One more compiles the whole pooled
Pong-v0 step, all three kernels inside, and asserts that its ops keep the
fused step's named scopes; one the fused DQN chunk of the
`dqn-cartpole-train` cell, whose ops keep the train step's four scopes,
which leave its instructions as they are. What the chip's compiler refuses
(a slice it cannot tile, an op it cannot lower, too much fast memory)
fails here.

Only one process at a time may load the TPU compiler's library. So the
topology is described inside a module fixture, never while a module is
imported, and every test of this kind stays in this one file. The
persistent compilation cache is off around these compiles: an entry
written for a described chip cannot be read back without one.
"""
import functools
import importlib.util
import os
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro import make_vec
from repro.core import make
from repro.kernels.envstep.framestack import frame_stack_pallas
from repro.kernels.envstep.megastep import megastep_pallas
from repro.kernels.envstep.specs import lookup
from repro.kernels.raster.raster import rasterize_pallas

BATCH, UNROLL = 4096, 32
FRAMES, SEGMENTS, HEIGHT, WIDTH = 1024, 6, 84, 84
#: the pooled pixel step: envs and steps per chunk
POOL_ENVS, POOL_UNROLL = 256, 4
#: the frame stack at the pixel cell's size: envs, steps, stack depth
STACK_ENVS, STACK_UNROLL, STACK = 1024, 8, 4
KERNELS = ("_megastep_kernel", "_raster_kernel", "_frame_stack_kernel")
SCOPES = ("cairl.reset", "cairl.layout", "cairl.megastep", "cairl.render",
          "cairl.frame_stack")


@functools.cache
def _trace_reduce():
    """The benchmark's trace reducer, which names a profile's kernels."""
    path = (pathlib.Path(__file__).resolve().parents[1] / "bench" /
            "trace_reduce.py")
    spec = importlib.util.spec_from_file_location("trace_reduce", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _kernels_in(text):
    """The names of the Pallas kernels a compiled program calls."""
    kernel_of = _trace_reduce().kernel_of
    return {kernel_of(line, KERNELS) for line in text.splitlines()} - {""}

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


def _compiled_text(fn, *shapes, sharding, dtypes=()):
    """`fn` compiled for f32 arguments of `shapes`, or of `dtypes` where
    given."""
    dtypes = dtypes or (jnp.float32,) * len(shapes)
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in zip(shapes, dtypes)]
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
    assert _kernels_in(text) == {"_megastep_kernel"}, env_id


def test_rasteriser_compiles_for_v5e(one_chip):
    text = _compiled_text(
        functools.partial(rasterize_pallas, h=HEIGHT, w=WIDTH),
        (FRAMES, SEGMENTS, 5), (FRAMES, SEGMENTS), sharding=one_chip)
    assert "tpu_custom_call" in text
    assert _kernels_in(text) == {"_raster_kernel"}


@pytest.mark.parametrize("b", (STACK_ENVS, 130))
def test_frame_stack_compiles_for_v5e(b, one_chip):
    """At the cell's size, and at a batch off the 128-lane tile, which
    Mosaic's strided loads refuse unless it is padded."""
    k, n = STACK_UNROLL, STACK
    f32 = jnp.float32
    text = _compiled_text(
        frame_stack_pallas, (b, n, HEIGHT, WIDTH), (k, b, HEIGHT, WIDTH),
        (k, b, HEIGHT, WIDTH), (k, b), sharding=one_chip,
        dtypes=(f32, f32, f32, jnp.bool_))
    assert "tpu_custom_call" in text
    assert _kernels_in(text) == {"_frame_stack_kernel"}


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """`backend="auto"` picks the Pallas kernels, as it does on a chip.
    Traces cached under either choice are dropped before and after."""
    from repro.kernels.envstep import ops as envstep_ops
    from repro.kernels.raster import ops as raster_ops

    jax.clear_caches()
    monkeypatch.setattr(envstep_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(raster_ops, "on_tpu", lambda: True)
    yield
    jax.clear_caches()


def test_pooled_pixel_step_keeps_its_scopes_for_v5e(one_chip, tpu_dispatch):
    h = make_vec("Pong-v0", POOL_ENVS, unroll=POOL_UNROLL).xla()
    placed = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                             sharding=one_chip)
    carry = jax.tree.map(placed, jax.eval_shape(
        h.init, jax.ShapeDtypeStruct((2,), jnp.uint32)))
    acts = placed(jax.ShapeDtypeStruct((POOL_UNROLL, POOL_ENVS), jnp.int32))
    text = jax.jit(h.step_many).lower(carry, acts).compile().as_text()
    assert _kernels_in(text) == set(KERNELS)
    op_names = {op for op, _ in _trace_reduce().hlo_op_names([text]).values()}
    for scope in SCOPES:
        assert any(scope in op for op in op_names), scope


def test_dqn_chunk_keeps_its_scopes_for_v5e(one_chip, tpu_dispatch):
    """The cell's 256 envs over the megastep, Table I's learner. The scopes
    are metadata here too: without them the chunk holds the same
    instructions. (At the default precision the compiler rounds the dots'
    float32 operands to bfloat16 first, so their shapes differ from
    these.)"""
    from test_trace_scopes import (assert_dqn_scoped, dqn_chunk_text,
                                   instruction_multiset, without_dqn_scopes)

    text = dqn_chunk_text(256, 4, sharding=one_chip)
    assert _kernels_in(text) == {"_megastep_kernel"}
    assert_dqn_scoped(text)
    assert "operand_precision={highest,highest}" in text
    with without_dqn_scopes():
        bare = dqn_chunk_text(256, 4, sharding=one_chip)
    assert instruction_multiset(text) == instruction_multiset(bare)
