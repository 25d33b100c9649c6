"""The reduction of a profiler trace to device busy time, per-op totals
and idle gaps (harness/tracefile.py), pinned on a trace written by hand
with known numbers and on a small trace recorded on the chip."""

import os

import pytest
from jax.profiler import ProfileData

from harness import tracefile
from harness.kernels import CAM_KERNEL

# Times in ps from the line's timestamp (1000 ns).  Host: the harness's
# window span [1000, 11000) ns and a client sink [3000, 6000) ns.  Chip 0:
# ops [1000, 2000), [1500, 2500) (the CAM kernel, overlapping) and
# [7000, 8000); chip 1: one op [2000, 4000).
XSPACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "client.sink" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "bacam_paged_topk_stage1" } }
  event_metadata { key: 3 value { id: 3 name: "jit_fn" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
"""


@pytest.fixture
def trace_path(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return str(path)


def test_reduction_of_a_known_trace(trace_path):
    s = tracefile.reduce(trace_path)
    assert s.chips == 2
    assert s.window_s == pytest.approx(10e-6)
    # chip 0 busy 1500 + 1000 ns, chip 1 2000 ns: mean 2250 ns
    assert s.busy_s == pytest.approx(2.25e-6)
    assert s.idle_share == pytest.approx(0.775)
    assert s.op_s == pytest.approx({"fusion.1": 2e-6,
                                    "bacam_paged_topk_stage1": 0.5e-6})
    assert s.kernel_s(("bacam_paged_topk_stage1",)) == pytest.approx(0.5e-6)
    # chip 0's holes: [2500, 7000) under the client sink, [8000, 11000)
    assert [g[0] for g in s.gaps] == ["client.sink", "nothing on the host"]
    assert [g[1] for g in s.gaps] == pytest.approx([4.5e-6, 3e-6])


def test_a_trace_without_the_window_span_is_refused(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        XSPACE.replace("bench.window", "other")))
    with pytest.raises(ValueError, match="bench.window"):
        tracefile.reduce(str(path))


def test_reduction_of_a_trace_recorded_on_the_chip():
    """0.25 s of bert-cam.decode on one TPU v5e: one decode step in
    flight, the CAM kernel the largest op."""
    path = os.path.join(tracefile.__file__.rsplit("/harness/", 1)[0],
                        "testdata", "decode_0.25s.xplane.pb")
    s = tracefile.reduce(path)
    assert s.chips == 1
    assert s.window_s == pytest.approx(0.250152432)
    assert s.busy_s == pytest.approx(0.240902227)
    assert s.kernel_s(CAM_KERNEL) == pytest.approx(0.189768919)
    top = sorted(s.op_s.items(), key=lambda kv: -kv[1])[:2]
    assert [k for k, _ in top] == ["%bacam_paged_topk_stage1.9 custom-call",
                                   "%copy.173 copy"]
    assert s.gaps[0] == ("nothing on the host", pytest.approx(0.009248505))
