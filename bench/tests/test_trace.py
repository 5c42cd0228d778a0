"""The trace reduction: busy time as a union of op intervals per device,
idle gaps put against the harness's host spans, on small traces written
out by hand in the reduction's input form."""
import numpy as np
import pytest

from harness import trace


def test_union_and_window_clipping():
    ev = {"devices": {"/device:TPU:0": [
        ["fusion.1", 0, 100], ["custom-call.2", 50, 100],    # overlap: 0-150
        ["fusion.3", 400, 100],                              # 400-500
        ["fusion.4", 950, 200]]},                            # clipped to 1000
        "anchor_ns": 0}
    spans = [("submit", 150, 300), ("poll", 300, 400), ("poll", 500, 600)]
    s = trace.reduce(ev, 0, 1000, spans)
    assert np.isclose(s.busy_s["/device:TPU:0"], (150 + 100 + 50) * 1e-9)
    assert np.isclose(s.window_s, 1000e-9)
    assert np.isclose(s.op_seconds(lambda n: "custom-call" in n), 100e-9)
    # idle: 150-400 (submit 150, poll 100), 500-950 (poll 100, client 350)
    assert s.idle_by_host == pytest.approx({"submit": 150e-9, "poll": 200e-9,
                                            "client": 350e-9})
    assert s.longest_gaps[0] == ("client", pytest.approx(450e-9))


def test_spans_move_onto_the_trace_clock():
    ev = {"devices": {"/device:TPU:0": [["op", 1000, 10]]}, "anchor_ns": 0}
    s = trace.reduce(ev, 1000, 1100, [("result", 10, 100)],
                     span_offset_ns=1000)
    assert s.idle_by_host == pytest.approx({"result": 90e-9})


def test_mean_over_devices():
    ev = {"devices": {"/device:TPU:0": [["a", 0, 100]],
                      "/device:TPU:1": [["a", 0, 50]]}, "anchor_ns": 0}
    s = trace.reduce(ev, 0, 200, [])
    assert np.isclose(s.mean_busy_s(), 75e-9)
    assert s.top_ops() == [["a", pytest.approx(150e-9)]]


def test_op_name_is_the_instruction_name():
    raw = ("%wcsd_query_ragged.1 = s32[1,1,8,128]{3,2,1,0:T(8,128)S(1)} "
           "custom-call(s32[1024]{0:T(1024)S(1)} %jit_emit_ragged_worklist_.0"
           ", ...), custom_call_target=\"tpu_custom_call\"")
    assert trace.op_name(raw) == "wcsd_query_ragged.1"
    assert trace.op_name("fusion.3") == "fusion.3"


def test_a_recorded_chip_trace():
    """100 ms of a road-uniform window's device ops, recorded on a TPU v5
    lite (op names already cut to the instruction name): busy is at most
    the window, and the roofline's matcher finds the ragged kernel."""
    import importlib.util
    import os

    from conftest import BENCH
    ev = trace.read_events(os.path.join(os.path.dirname(__file__), "data",
                                        "road-uniform-trace.json.gz"))
    lo = ev["anchor_ns"]
    s = trace.reduce(ev, lo, lo + 100e6)
    busy = s.busy_s["/device:TPU:0"]
    assert 0 < busy <= s.window_s
    spec = importlib.util.spec_from_file_location(
        "roofline", os.path.join(BENCH, "metrics", "ragged_roofline.lat.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    kernel = s.op_seconds(mod.is_kernel)
    assert 0 < kernel <= busy
    assert s.top_ops(1)[0][0].startswith("wcsd_query_ragged")
