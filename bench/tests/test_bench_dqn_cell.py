"""The `dqn-cartpole-train` cell resolved by name, and its readers of the
train step's scopes on small synthetic traces."""
import _paths
import pytest

import run
from trace_reduce import Op, Span, Trace

ROOT = _paths.ROOT
CELL = "dqn-cartpole-train"
SCOPE_READERS = {"act_us_per_update": "cairl.act",
                 "env_us_per_update": "cairl.env",
                 "replay_us_per_update": "cairl.replay",
                 "learn_us_per_update": "cairl.learn"}
READERS = set(SCOPE_READERS) | {"train_ops_per_update"}


def reader(name):
    return run.load_module("layer_metrics", name, (ROOT,)).read


def test_the_cell_resolves_by_name():
    found = run.resolve(CELL, (ROOT,))
    assert found["cell"]["chips"] == 1
    assert found["config"]["name"] == "dqn-cartpole-v1"
    assert found["config"]["reduced"] == []
    assert found["traffic"]["consumer"] == "dqn_train"
    assert hasattr(found["consumer"], "Cell")
    ref = found["reference"]
    assert callable(ref.check) and callable(ref.bind)
    assert {m["name"] for m in found["end_to_end"]} == {"env_steps_per_s",
                                                        "setup_s"}
    names = {m["name"] for m, _ in found["per_layer"]}
    # the metrics with no list of cells are read in every cell
    assert names == READERS | {"device_idle_share", "device_peak_gb"}
    for m, mod in found["per_layer"]:
        assert callable(mod.read)
        if m["name"] in READERS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "env_steps_per_s"


#: (op_name, ns) of one device's ops in one train step, in order
STEP = [
    ("jit(run_chunk)/while/body/closed_call/cairl.act/dot_general", 3000),
    ("jit(run_chunk)/while/body/closed_call/cairl.env/cairl.reset/add", 500),
    ("jit(run_chunk)/while/body/closed_call/cairl.env/cairl.megastep/"
     "_megastep_kernel/pallas_call", 1500),
    ("jit(run_chunk)/while/body/closed_call/cairl.replay/scatter", 4000),
    ("jit(run_chunk)/while/body/closed_call/cairl.learn/jvp()/dot_general",
     2000),
    ("jit(run_chunk)/while/body/closed_call/cairl.learn/transpose(jvp())/"
     "dot_general", 6000),
    ("jit(run_chunk)/while/body/dynamic_update_slice", 100),
]


def dqn_trace(n_devices=1, chunks=2, steps=10, step=STEP, kept=None):
    """`chunks` executions of a chunk program of `steps` train steps, each
    a `while` op holding its steps' ops; the profiler kept the first
    `kept` chunks' events."""
    devices = {}
    for d in range(n_devices):
        ops, t = [], 1000.0
        for _ in range(chunks if kept is None else kept):
            start = t
            for _ in range(steps):
                for op_name, dur in step:
                    ops.append(Op(f"op{len(ops)}", t, t + dur, op_name))
                    t += dur + 100
            ops.append(Op("while.25", start - 50, t, "jit(run_chunk)/while"))
            t += 10_000
        devices[f"/device:TPU:{d}"] = ops
    return Trace(devices, [Span("bench.window", 0, 10 ** 9)])


def ctx(tr, chunks=2, steps=10):
    return {"trace": tr, "stats": {"chunks": chunks},
            "train_steps_per_chunk": steps}


@pytest.mark.parametrize("n_devices", [1, 2])
@pytest.mark.parametrize("name,want", [
    ("act_us_per_update", 3.0), ("env_us_per_update", 2.0),
    ("replay_us_per_update", 4.0), ("learn_us_per_update", 8.0),
    ("train_ops_per_update", 6)])
def test_a_reader_gives_per_step_per_chip(name, want, n_devices):
    got = reader(name)(ctx(dqn_trace(n_devices)))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name,want", [
    ("act_us_per_update", 3.0), ("train_ops_per_update", 6)])
def test_a_trace_the_profiler_cut_reads_the_same(name, want):
    """The profiler kept 3 of the window's 8 chunks: per train step the
    reading is the same."""
    got = reader(name)(ctx(dqn_trace(chunks=8, kept=3), chunks=8))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_scopes_gives_nothing(name):
    """The parent's train step has no scopes: the readers give None."""
    bare = [("jit(run_chunk)/while/body/closed_call/dot_general", 3000)]
    assert reader(name)(ctx(dqn_trace(step=bare))) is None


def test_no_reader_is_another_scopes():
    for name, scope in SCOPE_READERS.items():
        for other in SCOPE_READERS.values():
            assert other == scope or scope not in other, name


def test_a_learner_below_float32_is_refused():
    """A program whose matmuls run at DEFAULT precision cannot run the
    configuration's float32 learner: the cell refuses it before its
    window."""
    check = run.load_module("consumers", "dqn_train",
                            (ROOT,)).check_float32_matmuls
    high = ('  %dot.1 = f32[256,32]{1,0} dot(%a, %b), lhs_contracting_dims='
            '{1}, rhs_contracting_dims={0}, operand_precision={highest,'
            'highest}\n')
    default = ('  %convolution.2 = f32[32,32]{1,0} convolution(%c, %d), '
               'dim_labels=bf_io->bf\n')
    check(high + "  %add.3 = f32[8]{0} add(%a, %b)\n")
    with pytest.raises(RuntimeError, match="not at float32"):
        check(high + default)
