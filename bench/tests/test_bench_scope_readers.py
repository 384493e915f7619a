"""The readers of the env step's named scopes and of the host round trip,
on small synthetic traces."""
import _paths
import pytest

import run
from trace_reduce import Op, Span, Trace

ROOT = _paths.ROOT
SCOPE_READERS = {"reset_us_per_chunk": "cairl.reset",
                 "layout_us_per_chunk": "cairl.layout",
                 "megastep_us_per_chunk": "cairl.megastep",
                 "render_us_per_chunk": "cairl.render",
                 "frame_stack_us_per_chunk": "cairl.frame_stack"}


def reader(name):
    return run.load_module("layer_metrics", name, (ROOT,)).read


def window(t1=10 ** 9):
    return Span("bench.window", 0, t1)


# -- the scopes --------------------------------------------------------------
#: (op_name, ns) of one device's ops, in order, 1000 ns apart
SCOPED = [
    ("jit(chunk)/bench.env_step/cairl.reset/while/body/add", 1000),
    # a nested jit's ops can carry a path without `bench.env_step`
    ("cairl.reset/jit(cumsum)/cumsum", 500),
    ("jit(chunk)/bench.env_step/cairl.layout/concatenate", 300),
    ("jit(chunk)/bench.env_step/shard_map/cairl.layout/transpose", 100),
    ("jit(chunk)/bench.env_step/cairl.megastep/_megastep_kernel/"
     "pallas_call", 2000),
    ("jit(chunk)/bench.policy/random_bits", 700),
    ("", 50),
]


def scoped_trace(n_devices=2, extra=()):
    devices = {}
    for d in range(n_devices):
        ops, t = [], 1000.0
        for op_name, dur in SCOPED + list(extra):
            ops.append(Op(f"op{len(ops)}", t, t + dur, op_name))
            t += dur + 1000
        devices[f"/device:TPU:{d}"] = ops
    return Trace(devices, [window()])


@pytest.mark.parametrize("name,want_ns", [
    ("reset_us_per_chunk", 1500), ("layout_us_per_chunk", 400),
    ("megastep_us_per_chunk", 2000), ("render_us_per_chunk", None),
    ("frame_stack_us_per_chunk", None)])
def test_scope_reader_on_a_state_env(name, want_ns):
    # four chunks: each device's time per chunk, not their sum
    got = reader(name)({"trace": scoped_trace(), "stats": {"chunks": 4}})
    assert got == (None if want_ns is None else
                   pytest.approx(want_ns / 4 * 1e-3))


def test_scope_readers_on_a_pixel_env():
    tr = scoped_trace(extra=[
        ("jit(chunk)/bench.env_step/cairl.render/jit(rasterize)/"
         "_raster_kernel/pallas_call", 4000),
        ("jit(chunk)/bench.env_step/cairl.frame_stack/while/body/select",
         8000)])
    ctx = {"trace": tr, "stats": {"chunks": 2}}
    assert reader("render_us_per_chunk")(ctx) == pytest.approx(2.0)
    assert reader("frame_stack_us_per_chunk")(ctx) == pytest.approx(4.0)


def test_scope_time_is_self_time():
    # a while under the reset scope [0, 10000) holds a fusion [1000, 4000)
    ops = [Op("while", 0, 10000, "jit(chunk)/cairl.reset/while"),
           Op("fusion", 1000, 4000, "jit(chunk)/cairl.reset/while/body/add"),
           Op("copy", 12000, 13000, "")]
    tr = Trace({"/device:TPU:0": ops}, [window()])
    got = reader("reset_us_per_chunk")({"trace": tr, "stats": {"chunks": 1}})
    assert got == pytest.approx(10.0)


def test_no_reader_is_another_scopes():
    for name, scope in SCOPE_READERS.items():
        for other in SCOPE_READERS.values():
            assert other == scope or scope not in other, name


# -- the host round trip ------------------------------------------------------
CHUNK = 100_000  # ns from one dispatch to the next
MIN_CHUNKS = run.load_module("layer_metrics", "round_trip_us_per_chunk",
                             (ROOT,)).MIN_CHUNKS


def round_trip_trace(n=40, launches=(10_000,), device=50_000, ready=5_000,
                     snapshot_at=(), shift=0, dispatch=3_000, late=None):
    """`n` chunks, one every CHUNK ns. Chunk i is dispatched at
    (i + 1) * CHUNK (a span of `dispatch` ns) and blocks from there until
    its program has ended on every device and `ready` ns have passed;
    device d starts it `launches[d]` ns (and `late[i]` more) after the
    dispatch started and runs it `device` ns, as its line shows it shifted
    by `shift` from the host's. A snapshot span precedes the dispatch of
    each chunk in `snapshot_at` and follows its block."""
    late = late or {}
    spans, modules = [window(n * CHUNK + 10 * CHUNK)], []
    devices = {f"/device:TPU:{d}": [] for d in range(len(launches))}
    for i in range(n):
        t = (i + 1) * CHUNK
        if i in snapshot_at:
            spans.append(Span("bench.snapshot", t - 2_000, t - 1_000))
        spans.append(Span("bench.dispatch", t, t + dispatch))
        for d, launch in enumerate(launches):
            s = t + launch + late.get(i, 0) + shift
            modules.append(Span("jit_chunk", s, s + device))
            devices[f"/device:TPU:{d}"].append(
                Op(f"fusion.{i}", s, s + device, "jit(chunk)/bench.env_step"))
        # a copy program around a snapshot is not the chunk's
        modules.append(Span("jit__lambda", t - 2_000, t - 1_500))
        end = t + max(launches) + late.get(i, 0) + device + ready
        spans.append(Span("bench.block", t + dispatch, end))
        if i in snapshot_at:
            spans.append(Span("bench.snapshot", end + 100, end + 900))
    return Trace(devices, spans, modules)


def round_trip(tr):
    return reader("round_trip_us_per_chunk")(
        {"trace": tr, "stats": {"chunks": 0}})


def test_round_trip_on_one_device():
    # 10 us to the chip's start and 5 us from its end to the block's return
    assert round_trip(round_trip_trace()) == pytest.approx(15.0)


def test_round_trip_on_four_devices_waits_for_the_last():
    # the chips start 10, 14, 12 and 11 us after the dispatch and run 50 us
    # each; the block returns 5 us after the last ends
    got = round_trip(round_trip_trace(launches=(10_000, 14_000, 12_000,
                                                11_000)))
    assert got == pytest.approx(19.0)


@pytest.mark.parametrize("shift", [-30_000, -1_000, 30_000, 250_000])
def test_round_trip_does_not_depend_on_the_clocks_offset(shift):
    """The chips' line can sit off the host's by more than the round trip's
    parts, or by more than a chunk: only lengths are read."""
    assert round_trip(round_trip_trace(shift=shift)) == pytest.approx(15.0)


def test_round_trip_uses_the_chips_mean_program_time():
    tr = round_trip_trace(launches=(10_000, 10_000))
    runs = [m for m in tr.modules if m.name == "jit_chunk"]
    # one chip's executions read 10 us longer: the mean program is 55 us
    longer = {id(m) for m in runs[::2]}
    tr.modules = [m._replace(end_ns=m.end_ns + 10_000)
                  if id(m) in longer else m for m in tr.modules]
    assert round_trip(tr) == pytest.approx(10.0)


def test_snapshot_chunks_are_left_out():
    """The copies around a checked chunk delay it and the chunk after it:
    both are left out, whatever they read."""
    late = {i: 30_000 for i in (3, 4, 17, 18)}
    got = round_trip(round_trip_trace(snapshot_at=(3, 17), late=late))
    assert got == pytest.approx(15.0)
    # not left out, they count
    got = round_trip(round_trip_trace(late=late))
    assert got == pytest.approx(15.0 + 30.0 * 4 / 40)


def test_a_missed_execution_moves_nothing():
    """The profiler can miss the program's first or any execution."""
    tr = round_trip_trace()
    runs = sorted((m for m in tr.modules if m.name == "jit_chunk"),
                  key=lambda m: m.start_ns)
    tr.modules = [m for m in tr.modules if m not in (runs[0], runs[9])]
    assert round_trip(tr) == pytest.approx(15.0)


@pytest.mark.parametrize("n,snapped,want", [
    (MIN_CHUNKS, (), 15.0), (MIN_CHUNKS - 1, (), None),
    # two chunks of 21 left out for one snapshot
    (MIN_CHUNKS + 1, (5,), None)])
def test_too_few_chunks_give_nothing(n, snapped, want):
    got = round_trip(round_trip_trace(n=n, snapshot_at=snapped))
    assert got == (None if want is None else pytest.approx(want))


def test_no_chunk_program_gives_nothing():
    tr = round_trip_trace()
    tr.modules = [m for m in tr.modules if m.name != "jit_chunk"]
    assert round_trip(tr) is None
