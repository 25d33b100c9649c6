"""Device time by jitted step and the engine thread's spans
(harness/steps.py), and the three readers built on them, pinned on a
trace written by hand with known numbers and on a small trace recorded
on the chip."""

import os

import pytest
from jax.profiler import ProfileData

from harness import cell as C
from harness import steps, tracefile
from harness.window import Window

# Times in ps from the line's timestamp (1000 ns).  Host: the window
# [1000, 11000) ns; the engine thread's plan [1100, 1300), dispatch
# [1300, 1500), readback [2500, 7000), ingest [7000, 7400), and a plan
# [10900, 11100) that leaves the window.  Chip 0: a prefill step
# [1500, 2500) holding ops [1500, 2000) and [2000, 2500) (the CAM kernel),
# a decode step [7000, 8000) holding one op, and a decode step
# [10500, 11500) that leaves the window, holding [10500, 11500).  Chip 1: a
# decode step [2000, 4000) holding one op.
XSPACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
  }
  lines { id: 2 name: "engine-runner" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 200000 }
    events { metadata_id: 3 offset_ps: 300000 duration_ps: 200000 }
    events { metadata_id: 4 offset_ps: 1500000 duration_ps: 4500000 }
    events { metadata_id: 5 offset_ps: 6000000 duration_ps: 400000 }
    events { metadata_id: 2 offset_ps: 9900000 duration_ps: 200000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "engine.plan" } }
  event_metadata { key: 3 value { id: 3 name: "engine.dispatch" } }
  event_metadata { key: 4 value { id: 4 name: "engine.readback" } }
  event_metadata { key: 5 value { id: 5 name: "engine.ingest" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 9500000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 500000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 9500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.16 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%bacam_paged_topk_stage1.9 = s32[8]{0} custom-call(s32[8]{0} %p)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_prefill_step(1069)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_decode_step(2207)" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.16 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "jit_decode_step(2207)" } }
}
"""

FUSION = "%fusion.16 fusion"
CAM = "%bacam_paged_topk_stage1.9 custom-call"


def _write(path, text=XSPACE):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


@pytest.fixture
def runs(tmp_path, monkeypatch):
    """A runs directory holding the trace where run.py leaves it."""
    monkeypatch.setattr(steps, "RUNS_DIR", str(tmp_path / "_runs"))
    path = _write(tmp_path / "_runs" / "trace-cell" / "plugins" / "profile"
                  / "1" / "h.xplane.pb")
    return path


def test_steps_of_a_known_trace(runs):
    s = steps.reduce(runs)
    assert s.chips == 2 and s.window_s == pytest.approx(10e-6)
    # wholly in the window: chip 0's prefill step, chip 0's first decode
    # step (1000 ns) and chip 1's (2000 ns); per chip, then / 2 chips
    assert s.module_n == {"jit_prefill_step": 0.5, "jit_decode_step": 1.0}
    assert s.module_s == pytest.approx({"jit_prefill_step": 0.5e-6,
                                        "jit_decode_step": 1.5e-6})
    assert s.step_ms("jit_prefill_step") == pytest.approx(1e-3)
    assert s.step_ms("jit_decode_step") == pytest.approx(1.5e-3)
    assert s.step_ms("jit_spec_step") is None
    # ops by the execution that holds them, clipped to the window: the
    # last decode op runs [10500, 11000) of it
    assert s.op_s == pytest.approx({
        ("jit_prefill_step", FUSION): 0.25e-6,
        ("jit_prefill_step", CAM): 0.25e-6,
        ("jit_decode_step", FUSION): (1000 + 500 + 2000) / 2 * 1e-9})
    assert steps.top_ops(s, 2) == [["jit_decode_step", FUSION,
                                    pytest.approx(1.75e-6)],
                                   ["jit_prefill_step", FUSION,
                                    pytest.approx(0.25e-6)]]
    # host spans wholly in the window; the plan that leaves it is out
    assert s.span_n == {"engine.plan": 1, "engine.dispatch": 1,
                        "engine.readback": 1, "engine.ingest": 1}
    assert s.span_s == pytest.approx({
        "engine.plan": 0.2e-6, "engine.dispatch": 0.2e-6,
        "engine.readback": 4.5e-6, "engine.ingest": 0.4e-6})


def test_an_op_outside_every_module_is_kept_apart(tmp_path):
    path = _write(tmp_path / "t.xplane.pb", XSPACE.replace(
        "events { metadata_id: 4 offset_ps: 6000000 duration_ps: 1000000 }",
        ""))
    s = steps.reduce(path)
    assert s.op_s[(steps.NO_MODULE, FUSION)] == pytest.approx(0.5e-6)


def test_the_idle_gap_under_a_readback_is_named_by_its_span(runs):
    """tracefile names chip 0's longest hole, [2500, 7000), by the engine
    thread's readback span that covers it."""
    g = tracefile.reduce(runs).gaps
    assert g[0] == ("engine.readback", pytest.approx(4.5e-6))


def _window(trace, ticks):
    return Window(t0=0.0, t1=1.0, records=[], before={"ticks": 0},
                  after={"ticks": ticks}, model={}, engine={},
                  device_kind="TPU v5 lite", matmul_weights_per_token=0,
                  trace=trace)


def test_the_step_and_host_readers(runs):
    w = _window(tracefile.reduce(runs), ticks=2)
    read = {n: C.reader(n)(w) for n in (
        "prefill_step_ms.decode", "decode_step_ms.decode",
        "engine_host_ms.decode")}
    assert read == pytest.approx({
        "prefill_step_ms.decode": 1e-3, "decode_step_ms.decode": 1.5e-3,
        # plan + dispatch + ingest = 800 ns over 2 ticks
        "engine_host_ms.decode": 0.4e-3})


def test_readers_read_nothing_without_their_trace(runs, tmp_path):
    # an untraced run
    w = _window(None, ticks=2)
    assert steps.for_window(w) is None
    # a trace of another window (a parent program's, say): no module or
    # engine span of the names read
    other = XSPACE.replace("duration_ps: 10000000", "duration_ps: 9000000")
    for name in ("jit_prefill_step", "jit_decode_step", "engine."):
        other = other.replace(f'name: "{name}', 'name: "x')
    path = _write(tmp_path / "_runs" / "trace-other" / "plugins" / "profile"
                  / "2" / "h.xplane.pb", other)
    os.utime(path, (2e9, 2e9))  # the newest
    w = _window(tracefile.reduce(path), ticks=2)
    assert steps.for_window(w).window_s == pytest.approx(9e-6)
    for n in ("prefill_step_ms.decode", "decode_step_ms.decode",
              "engine_host_ms.decode"):
        assert C.reader(n)(w) is None, n
    # the first trace is still found by its own window
    w = _window(tracefile.reduce(runs), ticks=2)
    assert steps.for_window(w).window_s == pytest.approx(10e-6)


def test_steps_of_a_trace_recorded_on_the_chip():
    """~56 ms of the tiny cell (tests/bench/tiny.py sizes) served by
    run.run_cell on one TPU v5e: two prefill and ten decode steps, the
    engine thread's spans, the host dispatching while the chip idles.
    Re-encoded after recording with only what the reductions read (the
    host planes and the chip's XLA Ops and XLA Modules lines; the
    compiled-module protos left out)."""
    path = os.path.join(os.path.dirname(tracefile.__file__), "..",
                        "testdata", "tiny_steps.xplane.pb")
    s = steps.reduce(path)
    assert s.chips == 1 and s.window_s == pytest.approx(0.05648686)
    assert s.module_n == {"jit_prefill_step": 2, "jit_decode_step": 10}
    assert s.step_ms("jit_prefill_step") == pytest.approx(0.559951)
    assert s.step_ms("jit_decode_step") == pytest.approx(0.1382674)
    assert steps.top_ops(s, 2) == [
        ["jit_decode_step", "%while.6 while", pytest.approx(0.000969665)],
        ["jit_decode_step", "%bacam_paged_topk_stage1.9 custom-call",
         pytest.approx(0.000594835)]]
    assert {k: v for k, v in s.span_n.items() if k.startswith("engine.")} == {
        "engine.drain": 11, "engine.plan": 11, "engine.dispatch": 10,
        "engine.readback": 12, "engine.ingest": 12}
    assert s.span_s["engine.dispatch"] == pytest.approx(0.04668786)
    # the chip idles while the engine thread dispatches the next tick
    g = tracefile.reduce(path).gaps
    assert g[0] == ("engine.dispatch", pytest.approx(0.005246621))
