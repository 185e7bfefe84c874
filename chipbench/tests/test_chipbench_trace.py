"""The trace reduction against small traces whose numbers are known."""

import os

import jax
import pytest
from jax.profiler import ProfileData

from chipbench import devtrace

# One TPU device plane and one host plane, in the layout the TPU profiler
# writes: ops on the "XLA Ops" line named by their HLO instruction, the
# harness's spans on a host thread.  Times in ns from 1000.
#   window          [1000, 21000)
#   fwht.1          [1000, 3000)   f32[8,32]
#   wv_step.2       [2000, 5000)   overlaps fwht by 1000
#   while.3         [1000, 20000)  control flow: not counted
#   fusion.4        [9000, 10000)
#   fwht.5          [15000, 16000) f32[16,32]
#   copy.6          [20000, 23000) clipped to the window at 21000
#   span deploy     [1000, 12000), span keep [12000, 21000)
# Busy: [1000, 5000) + [9000, 10000) + [15000, 16000) + [20000, 21000)
#   = 7000 ns; idle gaps: [5000, 9000) in deploy, [10000, 15000) in keep
#   (its midpoint 12500 lies there), [16000, 20000) in keep.
TEXT = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 19000000 }
    events { metadata_id: 4 offset_ps: 8000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 14000000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 19000000 duration_ps: 3000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 0 duration_ps: 22000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fwht.1 = f32[8,32]{1,0} custom-call(f32[8,32]{1,0} %p), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "%wv_step.2 = (f32[8,32]{1,0}, s32[8,32]{1,0}) custom-call(f32[8,32]{1,0} %a)" } }
  event_metadata { key: 3 value { id: 3 name: "%while.3 = (f32[8,32]) while(%t), condition=%c, body=%b" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = f32[8,32]{1,0} fusion(%x), kind=kLoop" } }
  event_metadata { key: 5 value { id: 5 name: "%fwht.5 = f32[16,32]{1,0} custom-call(f32[16,32]{1,0} %q)" } }
  event_metadata { key: 6 value { id: 6 name: "%copy.6 = f32[8,32]{1,0} copy(%y)" } }
  event_metadata { key: 7 value { id: 7 name: "jit_program(123)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 3 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 11000000 }
    events { metadata_id: 3 offset_ps: 11000000 duration_ps: 9000000 }
    events { metadata_id: 4 offset_ps: 500000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "chipbench.deploy" } }
  event_metadata { key: 3 value { id: 3 name: "chipbench.keep" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(f)" } }
}
"""


@pytest.fixture(scope="module")
def reduction(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TEXT))
    return devtrace.load(str(path))


def test_window_and_busy_union(reduction):
    assert reduction.window_s == pytest.approx(20000e-9)
    assert reduction.busy_s == pytest.approx(7000e-9)
    assert reduction.idle_share == pytest.approx(1 - 7000 / 20000)
    assert reduction.devices == 1


def test_control_flow_ops_are_left_out(reduction):
    assert "while" not in {op.name for op in reduction.ops}


def test_kernel_sums_and_shapes(reduction):
    fwht = reduction.kernel_events("fwht")
    assert [op.shapes()[0] for op in fwht] == [("f32", (8, 32)), ("f32", (16, 32))]
    assert sum(op.seconds for op in fwht) == pytest.approx(3000e-9)
    wv = reduction.kernel_events("wv_step")
    assert len(wv) == 1 and wv[0].seconds == pytest.approx(3000e-9)
    assert reduction.kernel_events("wv") == []


def test_top_ops_clip_to_the_window(reduction):
    top = dict(reduction.top_ops())
    assert top["copy"] == pytest.approx(1000e-9)
    assert top["fwht"] == pytest.approx(3000e-9)
    assert list(top)[0] in ("fwht", "wv_step")


def test_idle_gaps_by_span(reduction):
    idle = dict(reduction.top_idle())
    assert idle == pytest.approx({"chipbench.deploy": 4000e-9,
                                  "chipbench.keep": 9000e-9})
    assert sum(idle.values()) == pytest.approx(reduction.window_s - reduction.busy_s)


def test_cpu_recording_parses_and_names_the_missing_device(tmp_path):
    """A real recording (here of the CPU backend): the harness's spans
    are read from it, and the reduction refuses it for want of a TPU."""
    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    paths = [os.path.join(d, n) for d, _, ns in os.walk(tmp_path) for n in ns
             if n.endswith(".xplane.pb")]
    assert len(paths) == 1
    names = {e.name for p in ProfileData.from_file(paths[0]).planes
             for line in p.lines for e in line.events}
    assert "chipbench.window" in names
    with pytest.raises(ValueError, match="no TPU device plane"):
        devtrace.load(paths[0])
