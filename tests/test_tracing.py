"""The serving tracer (core/tracing.py): off by default and then holding
nothing per request; on, one consistent set of stamps per request and
spans per flush; bounded rings; and the always-on flush counters."""
import numpy as np
import pytest

from repro.checkpoint.fault import FaultSchedule, FaultyEngine
from repro.core import tracing
from repro.core.generators import erdos_renyi
from repro.core.query import DeviceQueryEngine
from repro.core.resilience import FlushRetryExhausted
from repro.core.serve import WCSDServer
from repro.core.tracing import Tracer
from repro.core.wc_index import build_wc_index


@pytest.fixture(scope="module")
def index():
    return build_wc_index(erdos_renyi(40, 3.0, num_levels=4, seed=2),
                          ordering="degree")


def _server(index, backend="device", **kw):
    base = dict(layout="csr", dispatch="ragged", use_pallas=True,
                interpret=True, backend=backend, max_batch=64)
    base.update(kw)
    return WCSDServer(index, **base)


def _queries(index, n, seed):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, index.num_nodes, n).astype(np.int32),
            rng.integers(0, index.num_nodes, n).astype(np.int32),
            rng.integers(0, index.num_levels, n).astype(np.int32))


def _spans(snap, name):
    sp = snap["spans"]
    sel = sp["name"] == tracing.SPANS.index(name)
    return {int(f): (int(a), int(b)) for f, a, b in
            zip(sp["flush"][sel], sp["start_ns"][sel], sp["end_ns"][sel])}


def test_tracer_off_holds_nothing_per_request(index):
    """Off (the default), no structure of the server grows with the
    number of requests served, and the tracer has no rings."""
    srv = _server(index)

    def sizes():
        return {k: len(v) for k, v in vars(srv).items()
                if isinstance(v, (dict, list, set))
                and k not in ("memo", "profile_memo")}

    srv.query_many(*_queries(index, 10, seed=1))
    before = sizes()
    for seed in range(2, 6):
        srv.query_many(*_queries(index, 50, seed=seed))
    assert sizes() == before
    assert not hasattr(srv, "_enqueue_t") and not hasattr(srv,
                                                          "latencies_us")
    snap = srv.tracer.snapshot()
    assert snap["requests"]["rid"].size == 0
    assert snap["spans"]["name"].size == 0
    assert srv.tracer._rid.size == 0


@pytest.mark.parametrize("backend", ["device", "sharded"])
def test_device_served_stamps_are_ordered(index, backend):
    """enqueue <= stage start <= launch end <= deliver end for every
    request the device served, through the continuous-batching path."""
    srv = _server(index, backend, max_wait_us=0.0, min_batch=4)
    srv.tracer.start()
    s, t, wl = _queries(index, 120, seed=3)
    rids = []
    for a, b, c in zip(s, t, wl):
        rids.append(srv.submit(int(a), int(b), int(c)))
        srv.poll()
    srv.flush()
    for r in rids:
        srv.result(r)
    snap = srv.tracer.snapshot()
    req = tracing.request_times(snap)
    launch = _spans(snap, "engine.launch")
    served = ~req["memo"] & ~req["rides"]
    assert served.sum() > 0 and len(req["rid"]) == len(rids)
    fl = snap["requests"]["flush"][np.isin(snap["requests"]["rid"],
                                           req["rid"][served])]
    launch_end = np.array([launch[int(f)][1] for f in fl])
    assert (req["enqueue_ns"][served] <= req["stage_ns"][served]).all()
    assert (req["stage_ns"][served] <= launch_end).all()
    assert (launch_end <= req["deliver_ns"][served]).all()
    # the flush counters and the flush ring agree
    st = srv.stats
    assert st.batches == len(snap["flushes"]["id"])
    causes = np.bincount(snap["flushes"]["cause"], minlength=4)
    assert tuple(causes) == (st.cap_flushes, st.opportunistic_flushes,
                             st.deadline_flushes, st.sync_flushes)
    assert (snap["flushes"]["Q"] >= snap["flushes"]["n"]).all()
    assert (snap["flushes"]["worklist_len"] > 0).all()


def test_memo_hits_and_riders_carry_their_flush(index):
    srv = _server(index)
    srv.tracer.start()
    a = srv.submit(1, 7, 2)
    a_dup = srv.submit(7, 1, 2)          # rides a's queued slot
    srv.flush_async()                     # flush 0: a in flight
    a_fly = srv.submit(1, 7, 2)           # rides the in-flight slot
    srv.flush()
    memo = srv.submit(7, 1, 2)            # answered from the memo
    for r in (a, a_dup, a_fly, memo):
        srv.result(r)
    req = srv.tracer.snapshot()["requests"]
    by_rid = dict(zip(req["rid"].tolist(), zip(req["flush"].tolist(),
                                               req["rides"].tolist())))
    assert by_rid[a] == (0, False)
    assert by_rid[a_dup] == (0, True) and by_rid[a_fly] == (0, True)
    assert by_rid[memo] == (tracing.MEMO, False)
    times = tracing.request_times(srv.tracer.snapshot())
    i = int(np.flatnonzero(times["rid"] == memo)[0])
    assert times["deliver_ns"][i] == times["enqueue_ns"][i]


def test_requeued_batch_keeps_its_first_enqueue(index):
    """A flush whose wait exhausts its retries puts its batch back in the
    queue; the requests keep their enqueue stamp and take the flush that
    retries them."""
    sched = FaultSchedule(fixed={0: "flush_hang", 1: "flush_hang"})
    srv = WCSDServer(engine=FaultyEngine(DeviceQueryEngine(index,
                                                           layout="csr"),
                                         sched),
                     flush_timeout_ms=5.0, max_retries=1,
                     backoff_base_ms=0.01)
    srv.tracer.start()
    rid = srv.submit(2, 9, 1)
    enq = int(srv.tracer.snapshot()["requests"]["enqueue_ns"][0])
    srv.flush_async()                     # flush 0 hangs twice
    with pytest.raises(FlushRetryExhausted):
        srv.flush()
    assert srv._pending_rids == {rid}
    srv.result(rid)                       # flush 1 carries it
    snap = srv.tracer.snapshot()
    assert snap["requests"]["enqueue_ns"].tolist() == [enq]
    assert snap["requests"]["flush"].tolist() == [1]
    times = tracing.request_times(snap)
    assert times["stage_ns"][0] == _spans(snap, "flush.stage")[1][0] > enq
    assert 0 not in _spans(snap, "drain.deliver")


@pytest.mark.parametrize("backend", ["device", "sharded"])
def test_engine_spans_nest_inside_their_flush(index, backend):
    srv = _server(index, backend)
    srv.tracer.start()
    for seed in range(3):
        srv.query_many(*_queries(index, 20, seed=seed))
    srv.query_profile_many([1, 2], [5, 6])
    snap = srv.tracer.snapshot()
    stage = _spans(snap, "flush.stage")
    sp = snap["spans"]
    names = [tracing.SPANS[k] for k in sp["name"]]
    for name, f, a, b in zip(names, sp["flush"], sp["start_ns"],
                             sp["end_ns"]):
        assert a <= b
        if name.startswith("engine."):
            lo, hi = stage[int(f)]
            assert lo <= a and b <= hi, (name, f)
    plan, launch = _spans(snap, "engine.plan"), _spans(snap, "engine.launch")
    assert set(plan) == set(launch) == set(stage)
    for f, (a, b) in _spans(snap, "engine.build").items():
        assert launch[f][0] <= a and b <= launch[f][1]
    assert all(plan[f][1] <= launch[f][0] for f in plan)


def test_ring_overflow_counts_dropped_and_does_not_grow():
    tr = Tracer(requests=8, spans=4, flushes=2)
    tr.start()
    sizes = (tr._rid.size, tr._span.shape, tr._flush.shape)
    for rid in range(20):
        tr.enqueue(rid, 100 + rid, tracing.PENDING)
    for fid in range(5):
        tr.open_flush(fid, "sync", 1)
        tr.span(tracing.STAGE, fid, fid + 1)
        tr.span(tracing.WAIT, fid + 1, fid + 2)
    assert (tr._rid.size, tr._span.shape, tr._flush.shape) == sizes
    snap = tr.snapshot()
    assert snap["dropped"] == (20 - 8) + (10 - 4) + (5 - 2)
    assert snap["requests"]["rid"].tolist() == list(range(12, 20))
    assert snap["spans"]["start_ns"].tolist() == [3, 4, 4, 5]
    assert snap["flushes"]["id"].tolist() == [3, 4]
    tr.reset()
    assert tr.snapshot()["dropped"] == 0
    assert tr.snapshot()["requests"]["rid"].size == 0


def test_new_programs_counts_each_shape_once(index):
    """Each (kind, padded batch, worklist length, gather capacity) an
    engine runs counts once; after a demotion the rung below runs its own
    programs and counts them once too."""
    srv = _server(index, "sharded")
    s, t, wl = _queries(index, 16, seed=9)

    def flush_once():
        srv.memo.clear()
        srv.query_many(s, t, wl)

    flush_once()
    flush_once()
    assert srv.stats.new_programs == 1
    srv.query_many(s[:3], t[:3], wl[:3])  # memo hits: no flush
    assert srv.stats.new_programs == 1
    srv.query_profile_many(s[:4], t[:4])
    assert srv.stats.new_programs == 2
    assert srv._demote() and srv.mode == "single_device"
    flush_once()
    flush_once()
    assert srv.stats.new_programs == 3
