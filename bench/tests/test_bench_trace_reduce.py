"""The reduction from a trace to numbers, on a small synthetic trace."""
import _paths  # noqa: F401
import pytest
from jax.profiler import ProfileData

import base64

from trace_reduce import (KERNEL_CALL, Op, Trace, hlo_op_names, idle_gaps,
                          union_ns, with_self_time)

# Two devices; times in ps inside a line that starts at 1000 ns.
# Device 0: fusion.1 [1000, 3000) ns under bench.env_step (tf_op stat),
# the kernel [2000, 4000) overlapping it, copy.2 [6000, 7000) named only
# through the HLO text (its event is named as the chip names it, by the
# instruction's text), and an op outside the window [20000, 21000).
# Device 1: one op [1000, 2000). Host: window [1000, 11000), a dispatch
# span [4000, 5000) and a block span [5000, 11000).
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000
             stats { metadata_id: 9 str_value: "jit(chunk)/bench.env_step/add" } }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 19000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "bench.env_step.1" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.2 = f32[8]{0} copy(f32[8]{0} %p)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_chunk" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.7" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 6000000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "bench.block" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(chunk)" } }
}
"""

BODY = base64.b64encode(b"\x00MLIR func _megastep_kernel \x01").decode()
HLO = f"""
  %copy.2 = f32[8]{{0}} copy(%p), metadata={{op_name="jit(chunk)/bench.policy/copy"}}
  %bench.env_step.1 = (f32[5,128]{{1,0}}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="jit(chunk)/bench.env_step/pallas_call"}}, backend_config={{"custom_call_config":{{"body":"{BODY}"}}}}
"""
KERNELS = ("_megastep_kernel", "_raster_kernel")


@pytest.fixture(scope="module")
def trace():
    return Trace.from_profile(ProfileData.from_text_proto(XSPACE), [HLO],
                              KERNELS)


def test_union_and_gaps():
    assert union_ns([(0, 2), (1, 4), (6, 7)]) == 5
    assert union_ns([]) == 0
    assert idle_gaps([(1, 2), (5, 6)], 0, 8) == [(0, 1), (2, 5), (6, 8)]
    assert idle_gaps([(0, 9)], 1, 8) == []


def test_window_devices_and_busy(trace):
    assert trace.window_s == pytest.approx(10e-6)
    assert trace.n_devices == 2
    # device 0: [1000, 4000) + [6000, 7000) = 4000 ns; device 1: 1000 ns;
    # the op at 20000 ns lies outside the window
    assert trace.busy_s() == pytest.approx(2500e-9)
    assert trace.idle_share() == pytest.approx(0.75)


def test_scope_attribution(trace):
    env = trace.ops(scope="bench.env_step")
    assert sorted(o.name for o in env) == ["bench.env_step.1", "fusion.1"]
    assert [o.name for o in trace.ops(scope="bench.policy")] == ["copy.2"]
    # overlapping, not nested: each keeps its whole duration
    assert trace.seconds(env) == pytest.approx(4000e-9 / 2)


def test_kernel_by_name(trace):
    kernels = trace.ops(kernel="_megastep_kernel")
    assert [o.name for o in kernels] == ["bench.env_step.1"]
    assert trace.ops(kernel="_raster_kernel") == []
    assert [o.name for o in trace.ops(scope="bench.env_step", kernel="")
            ] == ["fusion.1"]
    names = hlo_op_names([HLO], KERNELS)
    assert names["bench.env_step.1"][1] == "_megastep_kernel"
    assert names["copy.2"] == ("jit(chunk)/bench.policy/copy", "")
    # a kernel call whose kernel is not among the names given
    assert hlo_op_names([HLO])["bench.env_step.1"][1] == KERNEL_CALL


def test_self_time_of_nested_ops():
    # a while [0, 10) holding a fusion [1, 4) that holds a copy [2, 3),
    # and a copy [5, 9); then an op [12, 13)
    ops = [Op("while", 0, 10, ""), Op("fusion", 1, 4, ""),
           Op("copy", 2, 3, ""), Op("copy2", 5, 9, ""), Op("after", 12, 13, "")]
    self_ns = {o.name: o.self_ns for o in with_self_time(ops)}
    assert self_ns == {"while": 3, "fusion": 2, "copy": 1, "copy2": 4,
                       "after": 1}


def test_top_ops_and_named_gaps(trace):
    top = dict(trace.top_ops(10))
    # summed over devices, averaged: 2000 ns on device 0 over 2 devices
    assert top["fusion.1"] == pytest.approx(2000e-9 / 2)
    assert top["bench.env_step.1 (_megastep_kernel)"] == pytest.approx(1e-6)
    assert top["fusion.7"] == pytest.approx(1000e-9 / 2)
    gaps = trace.named_gaps(5)
    # device 0 idles over [4000, 6000) (dispatch, then block) and
    # [7000, 11000) (block); the longest first
    assert [round(s * 1e9) for _, s in gaps] == [4000, 2000]
    assert [n for n, _ in gaps] == ["bench.block", "bench.block"]


def test_no_window_span_is_an_error():
    bare = XSPACE.replace('name: "bench.window"', 'name: "other"')
    with pytest.raises(ValueError, match="bench.window"):
        Trace.from_profile(ProfileData.from_text_proto(bare))
