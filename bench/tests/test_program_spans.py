"""The server's own spans beside the harness's: the idle split by program
span on a hand-written trace, and, on a tiny CPU run, the program's
per-request stamps against the harness's submit and delivery stamps."""
import bisect

import numpy as np
import pytest

from harness import spans, trace

NAMES = ("flush.stage", "engine.plan", "engine.launch", "engine.build",
         "drain.wait", "drain.deliver")


def snap_of(rows):
    """A tracer snapshot's ``spans`` table from (name, start, end)."""
    return {"span_names": NAMES, "spans": {
        "name": np.array([NAMES.index(n) for n, _, _ in rows]),
        "start_ns": np.array([a for _, a, _ in rows]),
        "end_ns": np.array([b for _, _, b in rows]),
        "flush": np.zeros(len(rows), np.int64)}}


EVENTS = {"devices": {"/device:TPU:0": [["fusion.1", 0, 100],
                                        ["fusion.2", 400, 100]]},
          "anchor_ns": 0}
FLUSH = [("flush.stage", 150, 350), ("engine.plan", 160, 200),
         ("engine.launch", 200, 300), ("engine.build", 200, 250),
         ("drain.wait", 500, 600), ("drain.deliver", 600, 700)]


def test_idle_split_by_the_innermost_program_span():
    split = spans.idle_by_span(EVENTS, 0, 1000, snap_of(FLUSH))
    # idle: [100, 400) and [500, 1000)
    assert split == pytest.approx({
        "none": 400e-9, "flush.stage": 60e-9, "engine.plan": 40e-9,
        "engine.build": 50e-9, "engine.launch": 50e-9,
        "drain.wait": 100e-9, "drain.deliver": 100e-9})
    # the same trace through the existing reduction reads as before
    s = trace.reduce(EVENTS, 0, 1000)
    assert s.idle_by_host == pytest.approx({"client": 800e-9})
    idle = s.window_s - s.mean_busy_s()
    assert sum(split.values()) == pytest.approx(idle, rel=1e-12)


def test_program_spans_move_onto_the_trace_clock():
    split = spans.idle_by_span(EVENTS, 0, 1000, snap_of(
        [("drain.wait", -900, -800)]), offset_ns=1000)
    assert split["drain.wait"] == pytest.approx(100e-9)
    assert split["none"] == pytest.approx(700e-9)


def test_program_stamps_agree_with_the_harness(tiny_bench, quick):
    """Each request's program enqueue lies inside the harness's submit
    call, its program delivery at or before the harness found the answer,
    and queue wait plus flight is delivery minus enqueue to the
    nanosecond."""
    import jax

    from harness import drive
    from harness.cell_run import drive_mix, stand_up
    from harness.traffic import rng_for
    from repro.core import tracing

    cell = tiny_bench.cell("tiny-zipf")
    srv, src, _ = stand_up(tiny_bench, cell, 5, jax.devices()[:1])
    srv.tracer.start()
    clock = drive.HostClock()
    clock.span_from = 0.0
    req, _ = drive_mix(srv, cell.mix, src, rng_for(5, "window"), 0.5, clock)
    srv.tracer.stop()
    times = tracing.request_times(srv.tracer.snapshot())
    n = req.n
    assert n > 0 and times["rid"].tolist() == list(range(n))
    assert times["memo"].any() and (~times["memo"]).any()
    enq, stage, dlv = times["enqueue_ns"], times["stage_ns"], \
        times["deliver_ns"]
    assert ((stage - enq) + (dlv - stage) == dlv - enq).all()
    submits = sorted((a, b) for k, a, b in clock.spans if k == "submit")
    starts = [a for a, _ in submits]
    for k in range(n):
        a, b = submits[bisect.bisect_right(starts, int(enq[k])) - 1]
        assert a <= enq[k] <= b, k
        assert req.submit[k] * 1e9 <= enq[k] + 1000
    assert (dlv <= req.deliver[:n] * 1e9 + 1000).all()
