"""The fused env step names its sub-layers inside the compiled program.

`fused_step` and the pool's `step_many` put every op they emit under one
`jax.named_scope`; the scope reaches each HLO instruction's `op_name`
metadata, which is how a profile of the chip attributes device time to a
sub-layer (a substring match on the op's name path). Checked here on the
CPU's compiled program: one chip's worth of the pool for a state env and a
pixel env, and the sharded pool on four virtual CPU devices.
"""
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import make_vec

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
