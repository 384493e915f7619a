"""The host-clock readers of the window loop's own numbers."""
import _paths
import pytest

import run

ROOT = _paths.ROOT


def reader(name):
    return run.load_module("layer_metrics", name, (ROOT,)).read


def marks(times_ms):
    """(dispatch, ready) of back-to-back chunks taking `times_ms` each."""
    out, t = [], 0.0
    for ms in times_ms:
        out.append((t, t + ms / 1000))
        t += ms / 1000
    return out


#: a chunk of 1/512 s, so that sums are exact and 128 chunks span 250 ms
C = 1000 / 512


@pytest.mark.parametrize("times_ms,snapped,want", [
    # one stall of 32 chunks' time at chunk 300: the stretch from chunk 256
    # holds 44 chunks, the stall and 52 more, 250 ms over 97 chunks
    ([C] * 300 + [32 * C] + [C] * 300, (), 250 / 97),
    # steady chunks read their own time
    ([C] * 1000, (), C),
    # the stretch with the copies for the check is left out
    ([32 * C] + [C] * 1000, (0,), C),
    # under 250 ms in all: nothing to read
    ([C] * 100, (), None),
])
def test_slowest_stretch(times_ms, snapped, want):
    got = reader("chunk_ms_slowest_250ms")(
        {"stats": {"chunk_marks": marks(times_ms),
                   "snapshot_chunks": list(snapped)}})
    assert got == (None if want is None else pytest.approx(want))


def test_dispatch_mean():
    read = reader("dispatch_us_per_chunk")
    assert read({"stats": {"dispatch_s": [1e-4, 3e-4]}}) == pytest.approx(200)
    assert read({"stats": {"dispatch_s": []}}) is None
