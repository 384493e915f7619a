"""Byte counts from shapes, against hand counts, and the table of peaks."""
import _paths  # noqa: F401
import jax
import jax.numpy as jnp
import pytest

from peaks import PEAKS, V5E, peaks
from work import frame_bytes, roofline_seconds, step_interface_bytes


def interface_shapes(env_id: str, b: int, k: int):
    from repro import make_vec

    h = make_vec(env_id, b, unroll=k).xla()
    carry = jax.eval_shape(h.init, jax.random.PRNGKey(0))
    acts = jax.ShapeDtypeStruct((k, b), jnp.int32)
    after, out = jax.eval_shape(h.step_many, carry, acts)
    return carry, acts, after, out


@pytest.mark.parametrize("b,k", [(16384, 32), (256, 8)])
def test_cartpole_interface_bytes(b, k):
    # carry: 4 f32 state rows + int32 time + uint32[2] key per lane, the
    # f32[4] obs per lane, and the carry's own uint32[2] key
    carry = b * (4 * 4 + 4 + 8) + b * 16 + 8
    acts = k * b * 4
    # out: obs and terminal obs f32[4], f32 reward, bool done and truncated
    out = k * b * (16 + 16 + 4 + 1 + 1)
    assert step_interface_bytes(*interface_shapes("CartPole-v1", b, k)) == \
        2 * carry + acts + out


def test_pong_interface_bytes():
    b, k, frame = 1024, 8, 84 * 84 * 4
    # carry: 6 f32 game rows, int32 time, the f32 ring of 4 frames and a
    # uint32[2] key per lane, the stacked obs, and the carry's own key
    carry = b * (6 * 4 + 4 + 4 * frame + 8) + b * 4 * frame + 8
    acts = k * b * 4
    out = k * b * (4 * frame + 4 * frame + 4 + 1 + 1)
    assert step_interface_bytes(*interface_shapes("Pong-v0", b, k)) == \
        2 * carry + acts + out
    assert out > 1.8e9  # obs and terminal obs: 2 x 925 MB of frames a chunk


def test_frame_bytes_and_roofline():
    assert frame_bytes(8192, 84, 84, jnp.float32) == 8192 * 84 * 84 * 4
    assert frame_bytes(10, 84, 84, jnp.uint8) == 10 * 84 * 84
    pk = peaks(V5E)
    assert roofline_seconds(819e9, 0.0, pk) == pytest.approx(1.0)
    assert roofline_seconds(1.0, 197e12, pk) == pytest.approx(1.0)


def test_peaks_unknown_kind_raises():
    assert PEAKS[V5E]["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks("TPU v99")
