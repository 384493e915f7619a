"""The fused env step and the DQN train step name their sub-layers inside
the compiled program.

`fused_step` and the pool's `step_many` put every op they emit under one
`jax.named_scope`; the scope reaches each HLO instruction's `op_name`
metadata, which is how a profile of the chip attributes device time to a
sub-layer (a substring match on the op's name path). Checked here on the
CPU's compiled program: one chip's worth of the pool for a state env and a
pixel env, and the sharded pool on four virtual CPU devices. The DQN train
step (`rl/dqn.make_train_step`) puts its act, env, replay and learn parts
under four more, the env step's scopes nested in its env part; they are
metadata, so the fused chunk holds the same instructions as without them.
"""
import collections
import contextlib
import dataclasses
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import make_vec
from repro.configs.cairl_dqn import PAPER_TABLE_I
from repro.core import make
from repro.rl import dqn
from repro.train.fused import fused_train_chunk

SCOPES = ("cairl.reset", "cairl.layout", "cairl.megastep", "cairl.render",
          "cairl.frame_stack")
#: only the pixel pipeline renders and stacks frames
PIXEL_SCOPES = ("cairl.render", "cairl.frame_stack")
#: an HLO instruction: its opcode and its `op_name` metadata
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*.*?\s([\w\-]+)\(.*?"
                    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
#: what XLA names, inside a shard_map, an instruction that JAX lowered with
#: no name path (a scan's zero-filled output buffer, a loop counter): the
#: call's own op_name, with the instruction's HLO name appended
_SHARD_MAP_UNNAMED = re.compile(r".*/shard_map(/[\w\-]+\.\d+)?")
#: not computations: leaves and tuple plumbing of the program
_NOT_COMPUTE = {"parameter", "constant", "get-tuple-element", "tuple"}
ROOT = pathlib.Path(__file__).resolve().parents[1]
DQN_SCOPES = ("cairl.act", "cairl.env", "cairl.replay", "cairl.learn")
#: an HLO instruction's opcode and result shape
_OPCODE_SHAPE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s"
                           r"([\w\-]+)\(")


def step_many_text(env_id, num_envs, unroll, mesh=None) -> str:
    """The compiled program of the pool's K-step block."""
    h = make_vec(env_id, num_envs, unroll=unroll, mesh=mesh).xla()
    carry = jax.jit(h.init)(jax.random.PRNGKey(0))
    acts = jnp.zeros((unroll, num_envs), jnp.int32)
    return jax.jit(h.step_many).lower(carry, acts).compile().as_text()


def scoped_ops(text):
    """(opcode, op_name) of every instruction that JAX traced from the
    step: its name path starts at the jitted function."""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        opcode, op_name = m.groups()
        if (opcode in _NOT_COMPUTE or not op_name.startswith("jit(")
                or _SHARD_MAP_UNNAMED.fullmatch(op_name)):
            continue
        yield opcode, op_name


def assert_scoped(text, pixels: bool) -> None:
    want = set(SCOPES) if pixels else set(SCOPES) - set(PIXEL_SCOPES)
    seen = set()
    ops = list(scoped_ops(text))
    assert ops
    for opcode, op_name in ops:
        held = [s for s in SCOPES if s in op_name]
        assert len(held) == 1, (opcode, op_name)
        seen.update(held)
    assert seen == want


@pytest.mark.parametrize("env_id,num_envs,unroll,pixels", [
    ("CartPole-v1", 256, 8, False),
    ("Pong-v0", 16, 2, True),
])
def test_every_op_of_the_step_has_one_scope(env_id, num_envs, unroll,
                                            pixels):
    assert_scoped(step_many_text(env_id, num_envs, unroll), pixels)


_SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, {tests!r})
from repro.pool import default_pool_mesh
from test_trace_scopes import step_many_text

print(step_many_text("CartPole-v1", 256, 8, mesh=default_pool_mesh(4)))
"""


def test_sharded_pool_keeps_the_scopes():
    """Through `ShardedEnvPool`'s shard_map on four devices."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c",
         _SHARDED.format(tests=str(ROOT / "tests"))],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "devices=[4" in out.stdout  # the program is sharded four ways
    assert_scoped(out.stdout, pixels=False)


def test_no_scope_name_holds_another():
    """Readers match a scope by substring, so none may contain another."""
    for a in SCOPES:
        for b in SCOPES:
            assert a == b or a not in b


def test_an_unscoped_op_is_caught():
    line = ('  %add.1 = f32[8]{0} add(%a, %b), metadata={op_name='
            '"jit(f)/cairl.layout/cairl.reset/add"}\n'
            '  %mul.2 = f32[8]{0} multiply(%a, %b), metadata={op_name='
            '"jit(f)/mul"}\n')
    ops = list(scoped_ops(line))
    assert ops == [("add", "jit(f)/cairl.layout/cairl.reset/add"),
                   ("multiply", "jit(f)/mul")]
    with pytest.raises(AssertionError):
        assert_scoped(line, pixels=False)


# -- the DQN train step ---------------------------------------------------------
def dqn_chunk_text(num_envs=16, n=4, sharding=None, apply_fn=None) -> str:
    """The compiled fused chunk of Table I's DQN on CartPole-v1 over the
    fused pool, with the carry placed on `sharding`'s device when given."""
    env = make("CartPole-v1")
    cfg = dataclasses.replace(PAPER_TABLE_I, num_envs=num_envs,
                              env_backend="auto")
    fns = []

    def init(key):
        state, fn = dqn.dqn_init(env, cfg, key)
        fns.append(fn)
        return state

    carry = jax.eval_shape(init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    if sharding is not None:
        carry = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), carry)
    step = dqn.make_train_step(env, apply_fn or fns[0], cfg)
    return fused_train_chunk(step).lower(carry, n).compile().as_text()


def assert_dqn_scoped(text) -> None:
    """Each of the four scopes holds ops; no op holds two of them, and the
    env step's own scopes lie inside `cairl.env`."""
    seen = set()
    for opcode, op_name in scoped_ops(text):
        held = [s for s in DQN_SCOPES if s in op_name]
        assert len(held) <= 1, (opcode, op_name)
        if any(s in op_name for s in SCOPES):
            assert held == ["cairl.env"], (opcode, op_name)
        seen.update(held)
    assert seen == set(DQN_SCOPES)


def instruction_multiset(text) -> collections.Counter:
    """(opcode, result shape) of every instruction of a compiled program."""
    return collections.Counter(
        (m.group(2), m.group(1)) for m in map(_OPCODE_SHAPE.match,
                                              text.splitlines()) if m)


@contextlib.contextmanager
def without_dqn_scopes():
    """`jax.named_scope` of the train step's four scopes does nothing."""
    real = jax.named_scope

    def scope(name):
        return contextlib.nullcontext() if name in DQN_SCOPES else real(name)

    jax.named_scope = scope
    try:
        yield
    finally:
        jax.named_scope = real


def parent_mlp_apply(params, x, activation="elu"):
    """`mlp_apply` with its dots at the default precision."""
    for i, layer in enumerate(params):
        x = x @ layer["w"] + layer["b"]
        if i < len(params) - 1:
            x = jax.nn.elu(x)
    return x


def test_every_op_of_the_dqn_step_has_one_learner_scope():
    assert_dqn_scoped(dqn_chunk_text())


def test_dqn_scopes_and_precision_change_no_instruction():
    """On the CPU, the chunk with the scopes and HIGHEST dots holds the
    same instructions as the one without them, at the default precision."""
    scoped = dqn_chunk_text()
    with without_dqn_scopes():
        bare = dqn_chunk_text(apply_fn=lambda p, x: parent_mlp_apply(p, x))
    assert all(s not in bare for s in DQN_SCOPES)
    assert instruction_multiset(scoped) == instruction_multiset(bare)


def test_no_dqn_scope_name_holds_another():
    names = SCOPES + DQN_SCOPES
    for a in names:
        for b in names:
            assert a == b or a not in b
