"""WCSDServer semantics: memo hits + LRU eviction, power-of-two flush
padding, result() forcing a flush, and CSR-layout serving correctness."""
import numpy as np
import pytest

from repro.core.generators import scale_free
from repro.core.resilience import FlushRetryExhausted, UnknownRequestError
from repro.core.serve import WCSDServer
from repro.core.wc_index import build_wc_index


@pytest.fixture(scope="module")
def small_index():
    return build_wc_index(scale_free(120, 3, num_levels=4, seed=5),
                          ordering="degree")


# ------------------------------------------------------------------- memo
def test_memo_hit_skips_device(small_index, serve_layout):
    srv = WCSDServer(small_index, max_batch=64, layout=serve_layout)
    r1 = srv.submit(3, 9, 1)
    srv.flush()
    batches_before = srv.stats.batches
    r2 = srv.submit(3, 9, 1)          # memoized -> no pending, no flush
    assert srv.stats.memo_hits == 1
    assert srv.pending == []
    assert srv.result(r2) == srv.result(r1)
    assert srv.stats.batches == batches_before


def test_memo_is_symmetric(small_index, serve_layout):
    srv = WCSDServer(small_index, max_batch=64, layout=serve_layout)
    srv.submit(7, 2, 0)
    srv.flush()
    srv.submit(2, 7, 0)               # reversed endpoints hit the same key
    assert srv.stats.memo_hits == 1


def test_memo_distinguishes_levels(small_index, serve_layout):
    srv = WCSDServer(small_index, max_batch=64, layout=serve_layout)
    srv.submit(7, 2, 0)
    srv.flush()
    srv.submit(7, 2, 1)               # different level -> miss
    assert srv.stats.memo_hits == 0


def test_memo_lru_eviction(small_index, serve_layout):
    srv = WCSDServer(small_index, max_batch=1024, memo_capacity=4,
                     layout=serve_layout)
    for i in range(6):                 # 6 distinct keys through capacity 4
        srv.submit(i, i + 10, 0)
    srv.flush()
    assert len(srv.memo) == 4
    # oldest two evicted, newest four retained
    assert (0, 10, 0) not in srv.memo and (1, 11, 0) not in srv.memo
    assert (5, 15, 0) in srv.memo
    # re-submitting an evicted key is a miss; a retained key is a hit
    srv.submit(0, 10, 0)
    assert srv.stats.memo_hits == 0
    srv.submit(5, 15, 0)
    assert srv.stats.memo_hits == 1


def test_memo_hit_refreshes_lru_order(small_index, serve_layout):
    srv = WCSDServer(small_index, max_batch=1024, memo_capacity=2,
                     layout=serve_layout)
    srv.submit(1, 11, 0)
    srv.submit(2, 12, 0)
    srv.flush()
    srv.submit(1, 11, 0)               # hit refreshes (1, 11, 0)
    srv.submit(3, 13, 0)               # inserting a third evicts (2, 12, 0)
    srv.flush()
    assert (1, 11, 0) in srv.memo
    assert (2, 12, 0) not in srv.memo


# ------------------------------------------------------------------ flush
def test_flush_pads_to_power_of_two(small_index):
    srv = WCSDServer(small_index, max_batch=1024)
    seen = []
    inner = srv.engine.query_async   # bound class method, pre-stub
    # stub out the async handle so the server takes the blocking-query
    # fallback path through the instrumented lambda
    srv.engine.query_async = None
    srv.engine.query = lambda s, t, w: (seen.append(len(np.asarray(s)))
                                        or inner(s, t, w).wait())
    key = 0
    for n, want in [(1, 1), (2, 2), (3, 4), (5, 8), (8, 8), (9, 16)]:
        for _ in range(n):             # fresh keys -> every submit a miss
            srv.submit(key, key + 1, 0)
            key += 2
        srv.flush()
        assert seen[-1] == want, (n, seen[-1])


def test_flush_at_max_batch(small_index, serve_layout):
    srv = WCSDServer(small_index, max_batch=4, layout=serve_layout)
    rng = np.random.default_rng(0)
    for i in range(4):                 # distinct keys -> 4 misses
        srv.submit(int(rng.integers(50)), int(60 + i), 0)
    assert srv.stats.batches == 1      # auto-flushed on hitting max_batch
    assert srv.pending == []


def test_result_forces_flush(small_index, serve_layout):
    srv = WCSDServer(small_index, max_batch=1024, layout=serve_layout)
    rid = srv.submit(4, 8, 1)
    assert srv.pending and srv.stats.batches == 0
    got = srv.result(rid)              # pending rid -> flush happens inline
    assert got is not None
    assert srv.stats.batches == 1
    assert srv.pending == []
    with pytest.raises(UnknownRequestError):  # unknown rid: typed error
        srv.result(12345)


def test_result_unknown_rid_never_flushes_pending(small_index, serve_layout):
    """Regression for the O(pending) scan fix: an unknown rid must raise
    WITHOUT flushing the queued requests, however many are pending."""
    srv = WCSDServer(small_index, max_batch=1024, layout=serve_layout)
    for i in range(37):
        srv.submit(i, i + 40, 0)
    assert len(srv.pending) == 37
    with pytest.raises(UnknownRequestError, match="999999"):
        srv.result(999_999)
    assert len(srv.pending) == 37      # untouched
    assert srv.stats.batches == 0


def test_pending_rid_set_tracks_queue(small_index, serve_layout):
    """The pending-rid set mirrors the pending list through submit / memo
    hit / auto-flush / result-before-flush."""
    srv = WCSDServer(small_index, max_batch=4, layout=serve_layout)
    r1 = srv.submit(1, 21, 0)
    assert srv._pending_rids == {r1}
    srv.flush()
    assert srv._pending_rids == set()
    r2 = srv.submit(1, 21, 0)          # memo hit: never enters the queue
    assert srv._pending_rids == set() and srv.result(r2) == srv.result(r1)
    rids = [srv.submit(i, i + 50, 0) for i in range(2, 6)]  # hits max_batch
    assert srv.stats.batches == 2 and srv._pending_rids == set()
    r3 = srv.submit(9, 33, 1)
    assert srv.result(r3) is not None  # result-before-flush still works
    assert srv._pending_rids == set()
    assert all(srv.result(r) is not None for r in rids)


# -------------------------------------------------------------- directed
def test_directed_mode_keeps_memo_keys_apart(small_index):
    """undirected=False must not canonicalize (s, t): on a directed graph
    d(s, t) != d(t, s) and the swap would alias distinct answers. The
    engine is stubbed with an asymmetric function to simulate that."""
    srv = WCSDServer(small_index, max_batch=1024, undirected=False)
    srv.engine.query_async = None   # force the blocking-query fallback
    srv.engine.query = lambda s, t, w: np.asarray(s) * 1000 + np.asarray(t)
    a = srv.submit(2, 7, 0)
    srv.flush()
    b = srv.submit(7, 2, 0)            # NOT a memo hit in directed mode
    assert srv.stats.memo_hits == 0
    srv.flush()
    assert srv.result(a) == 2007 and srv.result(b) == 7002
    # an exact repeat IS still memoized
    c = srv.submit(2, 7, 0)
    assert srv.stats.memo_hits == 1 and srv.result(c) == 2007


def test_undirected_gate_still_canonicalizes_by_default(small_index):
    srv = WCSDServer(small_index, max_batch=64)
    assert srv.undirected
    r1 = srv.submit(11, 3, 1)
    srv.flush()
    r2 = srv.submit(3, 11, 1)
    assert srv.stats.memo_hits == 1
    assert srv.result(r1) == srv.result(r2)


# ------------------------------------------------------------ correctness
@pytest.mark.parametrize("layout", ["padded", "csr"])
def test_query_many_matches_oracle(small_index, layout):
    g_queries = random_queries_for(small_index, 300, seed=9)
    srv = WCSDServer(small_index, max_batch=64, layout=layout)
    s, t, wl = g_queries
    got = srv.query_many(s, t, wl)
    exp = small_index.query_batch(s, t, wl)
    assert np.array_equal(got, exp)
    assert srv.stats.requests == 300
    assert srv.stats.batches >= 1


def test_serve_from_packed_index_no_repack():
    """A PackedWCIndex from the device-resident builder is served as-is:
    the engine adopts the store object (no repack) and answers match the
    padded oracle."""
    from repro.core.generators import erdos_renyi
    from repro.core.wc_index_batched import build_wc_index_batched_packed

    g = erdos_renyi(90, 3.5, num_levels=4, seed=8)
    pidx, _ = build_wc_index_batched_packed(g, batch_size=16)
    srv = WCSDServer(pidx, max_batch=64, layout="csr")
    assert srv.engine.packed is pidx.labels   # same object, zero repack
    s, t, wl = random_queries_for(pidx, 200, seed=4)
    got = srv.query_many(s, t, wl)
    exp = pidx.to_index().query_batch(s, t, wl)
    assert np.array_equal(got, exp)


def random_queries_for(idx, n, seed):
    rng = np.random.default_rng(seed)
    s = rng.integers(0, idx.num_nodes, n).astype(np.int32)
    t = rng.integers(0, idx.num_nodes, n).astype(np.int32)
    wl = rng.integers(0, idx.num_levels, n).astype(np.int32)
    return s, t, wl


# ------------------------------------------------------- result eviction
def test_results_do_not_grow_across_epochs(small_index, serve_layout):
    """Regression for the unbounded-results leak: delivered rids are popped
    (read-once), so the dict stays empty after each query_many epoch
    instead of accumulating one entry per request forever."""
    srv = WCSDServer(small_index, max_batch=32, layout=serve_layout)
    s, t, wl = random_queries_for(small_index, 100, seed=1)
    for epoch in range(3):
        srv.query_many(s, t, wl)
        assert len(srv.results) == 0, epoch
    assert srv.stats.requests == 300


def test_result_is_read_once(small_index, serve_layout):
    srv = WCSDServer(small_index, max_batch=64, layout=serve_layout)
    rid = srv.submit(3, 9, 1)
    first = srv.result(rid)
    assert first is not None
    with pytest.raises(UnknownRequestError):   # delivered -> evicted
        srv.result(rid)
    # the memo still answers a re-submission without device work
    rid2 = srv.submit(3, 9, 1)
    assert srv.stats.memo_hits == 1 and srv.result(rid2) == first


# ----------------------------------------------------------- async flush
def test_auto_flush_is_async_and_double_buffered(small_index, serve_layout):
    """Hitting max_batch dispatches the batch (batches increments, pending
    clears) but does NOT materialize results; the host keeps queueing the
    next batch while one is in flight, and at most one is in flight."""
    srv = WCSDServer(small_index, max_batch=4, layout=serve_layout)
    rids = [srv.submit(i, i + 30, 0) for i in range(4)]
    assert srv.stats.batches == 1
    assert srv._inflight is not None       # dispatched, not drained
    assert len(srv.results) == 0           # nothing materialized yet
    more = [srv.submit(i + 10, i + 60, 0) for i in range(4)]  # batch k+1
    assert srv.stats.batches == 2          # launching k+1 drained k
    assert all(r in srv.results for r in rids)
    out = [srv.result(r) for r in rids + more]   # drains batch k+1
    assert all(o is not None for o in out)
    assert srv._inflight is None and len(srv.results) == 0


def test_duplicate_submitted_while_in_flight_hits_memo(small_index,
                                                       serve_layout):
    """A hot key re-submitted while its batch is still in flight must
    piggyback on the in-flight computation (a memo hit), not queue a
    second device batch — the heavy-tailed workload the memo exists for."""
    srv = WCSDServer(small_index, max_batch=2, layout=serve_layout)
    r1 = srv.submit(3, 9, 1)
    srv.submit(5, 11, 0)               # hits max_batch -> async dispatch
    assert srv._inflight is not None and srv.stats.batches == 1
    r3 = srv.submit(3, 9, 1)           # duplicate of in-flight r1
    assert srv.stats.memo_hits == 1
    assert srv.pending == []           # piggybacked, not re-queued
    got3 = srv.result(r3)              # drains the in-flight batch
    assert got3 is not None and got3 == srv.result(r1)
    assert srv.stats.batches == 1      # no second device batch


def test_async_results_match_sync(small_index, serve_layout):
    s, t, wl = random_queries_for(small_index, 200, seed=3)
    srv = WCSDServer(small_index, max_batch=16, layout=serve_layout)
    got = srv.query_many(s, t, wl)           # many async auto-flushes
    exp = small_index.query_batch(s, t, wl)
    assert np.array_equal(got, exp)


# ------------------------------------------------------- engine plumbing
def test_interpret_and_backend_plumbing(small_index):
    """Regression: serving must be able to reach the compiled kernel path —
    use_pallas / interpret / layout flow through to the engine instead of
    being hardwired."""
    srv = WCSDServer(small_index, layout="csr", use_pallas=True,
                     interpret=False)
    assert srv.engine.use_pallas and srv.engine.interpret is False
    assert srv.engine.layout == "csr"
    srv2 = WCSDServer(small_index, interpret=True)
    assert srv2.engine.interpret is True
    from repro.core.query import DeviceQueryEngine, ShardedQueryEngine
    from repro.launch.mesh import make_serving_mesh
    assert isinstance(srv.engine, DeviceQueryEngine)
    srv3 = WCSDServer(small_index, backend="sharded", layout="csr",
                      interpret=False, mesh=make_serving_mesh())
    assert isinstance(srv3.engine, ShardedQueryEngine)
    assert srv3.engine.interpret is False
    with pytest.raises(ValueError):
        WCSDServer(small_index, backend="nope")


def test_prebuilt_engine_injection(small_index):
    from repro.core.query import DeviceQueryEngine
    eng = DeviceQueryEngine(small_index, layout="csr")
    srv = WCSDServer(engine=eng, max_batch=32)
    assert srv.engine is eng
    s, t, wl = random_queries_for(small_index, 50, seed=6)
    assert np.array_equal(srv.query_many(s, t, wl),
                          small_index.query_batch(s, t, wl))


# ------------------------------------------------------------ edge cases
def test_empty_batch_paths(small_index, serve_layout):
    """Empty pending through flush()/flush_async(), and an empty
    query_many, must be no-ops."""
    srv = WCSDServer(small_index, max_batch=8, layout=serve_layout)
    srv.flush()
    srv.flush_async()
    assert srv.stats.batches == 0
    out = srv.query_many(np.array([], np.int32), np.array([], np.int32),
                         np.array([], np.int32))
    assert out.shape == (0,) and srv.stats.batches == 0


def test_plan_query_batch_empty():
    from repro.core.query import plan_query_batch
    bucket_of = np.zeros(10, np.int32)
    assert plan_query_batch(bucket_of, np.array([], np.int32),
                            np.array([], np.int32)) == []


def test_single_bucket_store_serves(small_index):
    """A store whose every label row fits one bucket exercises the planner's
    single-sub-batch path end to end."""
    packed = small_index.packed()
    assert packed.num_buckets == 1   # 120-vertex index: all rows < 128
    srv = WCSDServer(small_index, max_batch=32, layout="csr")
    s, t, wl = random_queries_for(small_index, 80, seed=2)
    assert np.array_equal(srv.query_many(s, t, wl),
                          small_index.query_batch(s, t, wl))


def test_duplicate_keys_both_orientations_one_flush(small_index):
    """undirected=True: both orientations of (s, t) plus exact duplicates
    inside ONE flush canonicalize to a single memo entry — and, with
    pending-batch dedup, a single device slot — and all get the same
    (correct) answer."""
    srv = WCSDServer(small_index, max_batch=1024, undirected=True)
    exp = int(small_index.query_batch(np.array([7]), np.array([2]),
                                      np.array([0]))[0])
    rids = [srv.submit(7, 2, 0), srv.submit(2, 7, 0),
            srv.submit(7, 2, 0), srv.submit(2, 7, 0)]
    assert srv.stats.memo_hits == 3          # piggybacked on the queued slot
    assert len(srv.pending) == 1             # ONE device slot for the key
    srv.flush()                              # one batch answers all four
    assert srv.stats.batches == 1
    assert srv.stats.max_batch == 1          # the batch held one real row
    assert [srv.result(r) for r in rids] == [exp] * 4
    assert (2, 7, 0) in srv.memo and (7, 2, 0) not in srv.memo
    assert len([k for k in srv.memo if k[2] == 0]) == 1


# --------------------------------------------------- pending-batch dedup
def test_pending_dedup_single_device_slot(small_index, serve_layout):
    """Regression (pending dedup): duplicates of a key submitted BEFORE
    any flush must ride the queued request's batch slot, not occupy extra
    device rows — pre-fix, the batch held three rows and memo_hits stayed
    0 until the flush landed."""
    srv = WCSDServer(small_index, max_batch=1024, layout=serve_layout)
    seen = []
    inner = srv.engine.query_async   # bound class method, pre-stub
    srv.engine.query_async = None
    srv.engine.query = lambda s, t, w: (seen.append(len(np.asarray(s)))
                                        or inner(s, t, w).wait())
    exp = int(small_index.query_batch(np.array([7]), np.array([2]),
                                      np.array([0]))[0])
    rids = [srv.submit(7, 2, 0), srv.submit(2, 7, 0), srv.submit(7, 2, 0)]
    assert len(srv.pending) == 1           # one slot for the hot key
    assert srv.stats.memo_hits == 2        # piggybacks count as hits
    srv.flush()
    assert seen[-1] == 1                   # device saw ONE row, not three
    assert [srv.result(r) for r in rids] == [exp] * 3


def test_pending_dedup_profiles(small_index, serve_layout):
    """The profile queue dedups pending pairs the same way (both
    orientations canonicalize onto one queued staircase)."""
    srv = WCSDServer(small_index, max_batch=1024, layout=serve_layout)
    seen = []
    inner = srv.engine.query_profile_async
    srv.engine.query_profile_async = None
    srv.engine.query_profile = lambda s, t: (seen.append(len(np.asarray(s)))
                                             or inner(s, t).wait())
    r1 = srv.submit_profile(4, 9)
    r2 = srv.submit_profile(9, 4)          # canonicalizes onto the queued pair
    r3 = srv.submit_profile(4, 9)
    assert len(srv.pending_profiles) == 1
    assert srv.stats.memo_hits == 2
    srv.flush()
    assert seen[-1] == 1
    a, b, c = (srv.profile_result(r) for r in (r1, r2, r3))
    assert a is not None and np.array_equal(a, b) and np.array_equal(a, c)


# ------------------------------------------------------ dispatch failure
def test_transient_dispatch_failure_is_absorbed(small_index, serve_layout):
    """The flush watchdog (docs/resilience.md): a single engine raise at
    dispatch time is retried with backoff inside flush() — the caller
    never sees it, the requests are answered, and the retry is counted."""
    srv = WCSDServer(small_index, max_batch=1024, layout=serve_layout,
                     backoff_base_ms=0.01)
    inner = srv.engine.query_async
    calls = {"n": 0}

    def flaky(s, t, w):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient dispatch failure")
        return inner(s, t, w)

    srv.engine.query_async = flaky
    rids = [srv.submit(i, i + 40, 0) for i in range(5)]
    srv.flush()                             # the raise is absorbed
    assert srv.stats.error_retries == 1
    assert srv.stats.demotions == 0 and srv.mode == "primary"
    assert srv.pending == []
    got = np.array([srv.result(r) for r in rids])
    s = np.arange(5, dtype=np.int32)
    exp = small_index.query_batch(s, s + 40, np.zeros(5, np.int32))
    assert np.array_equal(got, exp)
    assert calls["n"] == 2


def test_dispatch_failure_keeps_requests(small_index, serve_layout):
    """Regression (flush-path request loss): a terminally-failing dispatch
    — the retry budget exhausted on an engine= server, which has no
    fallback ladder to demote down — must leave every queued request
    pending (nothing dropped), and a later result() must still answer
    them once the engine recovers."""
    from repro.core.query import DeviceQueryEngine

    eng = DeviceQueryEngine(small_index, layout=serve_layout)
    calls = {"n": 0}

    class FlakyEngine:
        layout = serve_layout
        query_profile = eng.query_profile

        def query(self, s, t, w):
            calls["n"] += 1
            if calls["n"] <= 2:             # budget is 1 retry -> exhausted
                raise RuntimeError("dispatch failure")
            return eng.query(s, t, w)

    srv = WCSDServer(engine=FlakyEngine(), max_batch=1024,
                     max_retries=1, backoff_base_ms=0.01)
    assert srv.mode == "injected"           # no ladder to absorb the loss
    rids = [srv.submit(i, i + 40, 0) for i in range(5)]
    with pytest.raises(FlushRetryExhausted):
        srv.flush()
    assert srv.stats.error_retries == 1 and srv.stats.exhausted == 1
    assert len(srv.pending) == 5            # nothing dropped
    assert srv._pending_rids == set(rids)
    assert srv.stats.batches == 0           # the failed dispatch never landed
    got = np.array([srv.result(r) for r in rids])   # result() retries
    s = np.arange(5, dtype=np.int32)
    exp = small_index.query_batch(s, s + 40, np.zeros(5, np.int32))
    assert np.array_equal(got, exp)
    assert calls["n"] == 3


def test_profile_dispatch_failure_keeps_profiles(small_index, serve_layout):
    """Partial failure: the scalar half of a mixed flush dispatches, the
    profile dispatch raises until the budget is exhausted — the profile
    queue must survive intact and a retry must answer both halves."""
    srv = WCSDServer(small_index, max_batch=1024, layout=serve_layout,
                     backoff_base_ms=0.01)
    inner = srv.engine.query_profile_async
    calls = {"n": 0}

    def flaky(s, t):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("profile dispatch failure")
        return inner(s, t)

    srv.engine.query_profile_async = flaky
    rs = srv.submit(3, 9, 1)
    rp = srv.submit_profile(4, 11)
    srv.flush()                             # watchdog absorbs the raise
    assert srv.stats.error_retries == 1
    assert not srv.pending and not srv.pending_profiles
    prof = srv.profile_result(rp)
    assert prof is not None and len(prof) == small_index.num_levels + 1
    assert srv.result(rs) is not None
    assert calls["n"] == 2


# -------------------------------------------------------- latency stats
def test_flush_time_split_and_latency(small_index, serve_layout):
    """Host flush time splits into dispatch, drain wait and delivery, and
    with the tracer on every request gets an enqueue->deliver latency
    sample — memo hits included."""
    srv = WCSDServer(small_index, max_batch=16, layout=serve_layout)
    srv.tracer.start()
    s, t, wl = random_queries_for(small_index, 64, seed=12)
    srv.query_many(s, t, wl)
    st = srv.stats
    assert st.dispatch_time_s > 0.0 and st.drain_wait_s > 0.0
    assert st.deliver_time_s > 0.0
    assert st.delivered == st.requests - st.memo_hits + (
        srv.tracer.snapshot()["requests"]["rides"].sum())
    lat = srv.latency_summary()
    assert lat["count"] == 64               # all delivered -> all sampled
    assert lat["p99_us"] >= lat["p50_us"] >= 0.0
    assert srv.tracer.snapshot()["dropped"] == 0


# ---------------------------------------------------- continuous batching
class _Gate:
    """Controllable readiness probe injected into PendingResult deps, so
    tests decide when the 'device' looks done without real async work."""

    def __init__(self):
        self.ready = False

    def is_ready(self):
        return self.ready


def _gate_engine(srv):
    """Wrap engine.query_async so every dispatched handle reports ready()
    only once the returned gate is opened (wait() still works)."""
    from repro.core.query import PendingResult
    gate = _Gate()
    inner = srv.engine.query_async
    srv.engine.query_async = lambda s, t, w: PendingResult(
        inner(s, t, w).wait, deps=(gate,))
    return gate


def test_opportunistic_flush_below_max_batch(small_index, serve_layout):
    """With a deadline configured and the in-flight slot free, min_batch
    queued requests dispatch immediately — no waiting for max_batch."""
    srv = WCSDServer(small_index, max_batch=1024, layout=serve_layout,
                     max_wait_us=10_000_000.0, min_batch=3)
    rids = [srv.submit(i, i + 30, 0) for i in range(3)]
    assert srv.stats.batches == 1          # fired at min_batch, not 1024
    assert srv.stats.opportunistic_flushes == 1
    assert srv.stats.deadline_flushes == 0
    assert srv._inflight is not None and srv.pending == []
    assert all(srv.result(r) is not None for r in rids)


def test_below_min_batch_never_early_flushes(small_index, serve_layout):
    """min_batch is an admission floor: under it, even an expired deadline
    does not fire (max_batch remains the only trigger)."""
    srv = WCSDServer(small_index, max_batch=1024, layout=serve_layout,
                     max_wait_us=0.0, min_batch=4)
    for i in range(3):
        srv.submit(i, i + 30, 0)
    assert srv.stats.batches == 0 and len(srv.pending) == 3


def test_deadline_flush_with_busy_slot(small_index, serve_layout):
    """While a batch is in flight and its device work unfinished, newly
    queued requests flush on the max_wait_us deadline instead of waiting
    for the slot (or for max_batch)."""
    srv = WCSDServer(small_index, max_batch=1024, layout=serve_layout,
                     max_wait_us=0.0, min_batch=2)
    gate = _gate_engine(srv)
    first = [srv.submit(i, i + 50, 0) for i in range(2)]
    assert srv.stats.opportunistic_flushes == 1 and srv.stats.batches == 1
    assert not gate.ready                  # device "still computing"
    r5 = srv.submit(40, 90, 1)
    assert srv.stats.batches == 1          # below min_batch: still queued
    r6 = srv.submit(41, 91, 1)             # min_batch hit, slot busy, 0µs
    assert srv.stats.batches == 2
    assert srv.stats.deadline_flushes == 1
    gate.ready = True
    assert all(srv.result(r) is not None for r in first + [r5, r6])


def test_poll_harvests_and_flushes(small_index, serve_layout):
    """poll(): a finished in-flight batch is drained without blocking and
    the queued requests dispatch opportunistically into the freed slot."""
    srv = WCSDServer(small_index, max_batch=1024, layout=serve_layout,
                     max_wait_us=1e9, min_batch=1)
    gate = _gate_engine(srv)
    r1 = srv.submit(3, 9, 1)       # min_batch=1, slot free -> dispatches
    assert srv.stats.opportunistic_flushes == 1
    r2 = srv.submit(5, 11, 0)      # slot busy, huge deadline -> queued
    assert srv.stats.batches == 1 and len(srv.pending) == 1
    srv.poll()                     # busy slot: nothing happens
    assert srv.stats.batches == 1 and r1 not in srv.results
    gate.ready = True
    srv.poll()                     # harvests batch 1, dispatches batch 2
    assert r1 in srv.results       # delivered without result() blocking
    assert srv.stats.batches == 2
    assert srv.stats.opportunistic_flushes == 2
    assert srv.result(r1) is not None and srv.result(r2) is not None


def test_mixed_flush_single_slot_continuous(small_index, serve_layout):
    """An early flush carries the scalar AND profile queues together as
    the single in-flight slot (stats.batches counts the pair once)."""
    srv = WCSDServer(small_index, max_batch=1024, layout=serve_layout,
                     max_wait_us=0.0, min_batch=2)
    rs = srv.submit(3, 9, 1)
    rp = srv.submit_profile(4, 11)         # npend=2 -> early flush
    assert srv.stats.batches == 1
    assert srv._inflight is not None and srv._inflight_prof is not None
    assert srv.result(rs) is not None
    prof = srv.profile_result(rp)
    assert prof is not None and len(prof) == small_index.num_levels + 1


# ------------------------------------------- continuous-traffic harness
def _random_mutation(rng, g):
    """1-2 random inserts/deletes over ``g`` (valid levels only)."""
    inserts, deletes = [], []
    for _ in range(int(rng.integers(1, 3))):
        half = np.flatnonzero(g.edges_src < g.edges_dst)
        if rng.random() < 0.45 and len(half):
            e = int(rng.choice(half))
            deletes.append((int(g.edges_src[e]), int(g.edges_dst[e])))
        else:
            u, v = (int(x) for x in rng.choice(g.num_nodes, 2,
                                               replace=False))
            inserts.append((u, v, float(rng.choice(g.levels))))
    return inserts, deletes


@pytest.mark.parametrize("mode", ["device", "sharded", "dynamic"])
def test_continuous_traffic_differential(mode):
    """Randomized interleaved traffic — submit / submit_profile / result /
    poll (/ apply_updates in dynamic mode) — under deadline flushes,
    differentially checked against the BFS oracle grid, then the bulk
    query_many path over the same stream."""
    from repro.core.baselines import constrained_distance_grid
    from repro.core.generators import erdos_renyi

    g = erdos_renyi(40, 3.0, num_levels=3, seed=21)
    idx = build_wc_index(g, ordering="degree")
    kw = dict(max_batch=32, max_wait_us=0.0, min_batch=4, layout="csr",
              use_pallas=True, interpret=True)
    if mode == "sharded":
        from repro.launch.mesh import make_serving_mesh
        srv = WCSDServer(idx, backend="sharded", mesh=make_serving_mesh(),
                         **kw)
    elif mode == "dynamic":
        srv = WCSDServer(idx, graph=g, compact_threshold=None, **kw)
    else:
        srv = WCSDServer(idx, **kw)
    srv.tracer.start()

    rng = np.random.default_rng(77)
    grid = constrained_distance_grid(g)
    V, W = g.num_nodes, g.num_levels
    exp_scalar, exp_prof = {}, {}   # rid -> expectation at submit time
    out_scalar = {}                 # rid -> value read mid-stream
    unread = []                     # scalar rids not yet result()-ed
    submitted = []

    for step in range(160):
        op = rng.random()
        if op < 0.55:
            s, t = int(rng.integers(V)), int(rng.integers(V))
            wl = int(rng.integers(W))
            rid = srv.submit(s, t, wl)
            exp_scalar[rid] = int(grid[s, t, wl])
            unread.append(rid)
            submitted.append((s, t, wl))
        elif op < 0.72:
            s, t = int(rng.integers(V)), int(rng.integers(V))
            rid = srv.submit_profile(s, t)
            exp_prof[rid] = grid[s, t, :].copy()
        elif op < 0.84 and unread:
            rid = unread.pop(int(rng.integers(len(unread))))
            out_scalar[rid] = srv.result(rid)   # may force a flush
        elif op < 0.90:
            srv.poll()
        elif mode == "dynamic" and op < 0.93:
            ins, dels = _random_mutation(rng, srv.index.graph)
            srv.apply_updates(inserts=ins, deletes=dels)
            grid = constrained_distance_grid(srv.index.graph)
        # else: idle tick

    srv.flush()
    for rid in unread:
        out_scalar[rid] = srv.result(rid)
    for rid, exp in exp_scalar.items():
        assert out_scalar[rid] == exp, rid
    for rid, exp in exp_prof.items():
        got = srv.profile_result(rid)
        assert got is not None and np.array_equal(got, exp), rid

    # continuous batching actually fired below the hard cap
    assert srv.stats.opportunistic_flushes + srv.stats.deadline_flushes > 0
    assert srv.stats.max_batch < kw["max_batch"]
    lat = srv.latency_summary()
    assert lat["count"] == srv.stats.requests + srv.stats.profile_requests

    # the epoch-flush bulk path over the same scalar stream agrees with
    # the (final) oracle grid
    if submitted:
        s, t, wl = (np.array(x, np.int32) for x in zip(*submitted))
        assert np.array_equal(srv.query_many(s, t, wl), grid[s, t, wl])


# ------------------------------------------------------------- readback
class _CopyProbe:
    """A device-array stand-in that records `copy_to_host_async` calls and
    reports ready after ``ready_after`` `is_ready` probes."""

    def __init__(self, ready_after=0):
        self.copies = 0
        self.probes = 0
        self.ready_after = ready_after

    def copy_to_host_async(self):
        self.copies += 1

    def is_ready(self):
        self.probes += 1
        return self.probes > self.ready_after


def test_pending_result_starts_one_host_copy_per_dep():
    """Construction starts each dep's device-to-host copy exactly once;
    ready() and wait() do not start another."""
    from repro.core.query import PendingResult
    deps = [_CopyProbe(), _CopyProbe()]
    h = PendingResult(lambda: np.arange(3, dtype=np.int32), deps=deps)
    assert [d.copies for d in deps] == [1, 1]
    assert h.ready()
    assert np.array_equal(h.wait(), np.arange(3))
    assert [d.copies for d in deps] == [1, 1]


def test_pending_result_deps_without_copy_method():
    """Readiness probes without `copy_to_host_async` (the `_Gate` test
    deps, host values) are left alone and still gate ready()."""
    from repro.core.query import PendingResult
    gate, probe = _Gate(), _CopyProbe()
    h = PendingResult(lambda: np.int32(7), deps=(gate, probe, 3))
    assert probe.copies == 1
    assert not h.ready()
    gate.ready = True
    assert h.ready() and h.wait() == 7


def _sharded_labels_engine(idx):
    from repro.core.query import ShardedQueryEngine
    from repro.launch.mesh import make_serving_mesh
    eng = ShardedQueryEngine(idx, mesh=make_serving_mesh(), layout="csr",
                             device_budget_bytes=1)
    assert eng.mode == "sharded_labels"
    return eng


@pytest.mark.parametrize("path", ["ragged", "bucket_pair", "sharded_labels"])
def test_handle_wait_is_bit_identical(small_index, path):
    """With the copy started at dispatch, wait() still returns exactly
    np.asarray of the handle's device arrays, through its finalizer (the
    bucket-pair assembly, the row-sharded unpermute): the oracle's answers,
    same dtype, bit for bit."""
    from repro.core.query import DeviceQueryEngine
    if path == "sharded_labels":
        eng = _sharded_labels_engine(small_index)
    else:
        eng = DeviceQueryEngine(small_index, layout="csr", dispatch=path)
    s, t, wl = random_queries_for(small_index, 100, seed=31)
    h = eng.query_async(s, t, wl)
    raw = [np.asarray(d).copy() for d in h._deps]
    got = h.wait()
    exp = np.asarray(small_index.query_batch(s, t, wl))
    assert got.dtype == np.int32 and got.tobytes() == exp.astype(
        np.int32).tobytes()
    if path == "ragged":
        assert got.tobytes() == raw[0][:len(s)].tobytes()
    assert all(np.array_equal(np.asarray(d), r)
               for d, r in zip(h._deps, raw))


@pytest.mark.parametrize("timeout_ms", [None, 5000.0])
def test_readback_counters(small_index, serve_layout, timeout_ms):
    """Every flush is drained through one handle, and the readback is the
    part of the drain's wait after readiness: 0 < readback <= drain wait."""
    srv = WCSDServer(small_index, max_batch=16, layout=serve_layout,
                     flush_timeout_ms=timeout_ms)
    handles = []
    inner = srv.engine.query_async

    def counted(s, t, w):
        handles.append(inner(s, t, w))
        return handles[-1]

    srv.engine.query_async = counted
    s, t, wl = random_queries_for(small_index, 64, seed=13)
    srv.query_many(s, t, wl)
    st = srv.stats
    assert len(handles) >= 2 and st.batches == len(handles)
    assert 0.0 < st.readback_time_s <= st.drain_wait_s


def test_ready_handle_drained_without_sleep(small_index, serve_layout,
                                            monkeypatch):
    """Under the watchdog, a handle that turns ready after a few probes is
    drained with only a yield between probes: no sleep."""
    import repro.core.serve as serve_mod
    from repro.core.query import PendingResult
    srv = WCSDServer(small_index, max_batch=64, layout=serve_layout,
                     flush_timeout_ms=5000.0)
    probe = _CopyProbe(ready_after=25)
    inner = srv.engine.query_async
    srv.engine.query_async = lambda s, t, w: PendingResult(
        inner(s, t, w).wait, deps=(probe,))
    sleeps, yields = [], []
    monkeypatch.setattr(serve_mod.time, "sleep", sleeps.append)
    monkeypatch.setattr(serve_mod.os, "sched_yield",
                        lambda: yields.append(1))
    rid = srv.submit(3, 9, 1)
    srv.flush()
    assert probe.probes > 25 and sleeps == [] and len(yields) >= 25
    assert srv.stats.batches == 1 and srv.stats.timeout_retries == 0
    assert 0.0 < srv.stats.readback_time_s <= srv.stats.drain_wait_s
    assert srv.result(rid) == small_index.query_batch(
        np.array([3]), np.array([9]), np.array([1]))[0]


def test_wedged_handle_backs_off_then_times_out(small_index, serve_layout,
                                                monkeypatch):
    """A handle still not ready after SPIN_S of probing is polled every
    POLL_SLEEP_S, and the watchdog abandons it at its deadline exactly as
    before: one timeout retry, the re-dispatched batch answers."""
    import repro.core.serve as serve_mod
    from repro.core.query import PendingResult
    srv = WCSDServer(small_index, max_batch=64, layout=serve_layout,
                     flush_timeout_ms=40.0, backoff_base_ms=0.0, jitter=0.0)
    gate, n = _Gate(), {"calls": 0}
    inner = srv.engine.query_async

    def dispatch(s, t, w):
        n["calls"] += 1
        h = inner(s, t, w)
        return PendingResult(h.wait, deps=(gate,)) if n["calls"] == 1 else h

    srv.engine.query_async = dispatch
    sleeps = []
    real_sleep = serve_mod.time.sleep
    monkeypatch.setattr(serve_mod.time, "sleep",
                        lambda sec: (sleeps.append(sec), real_sleep(sec)))
    rid = srv.submit(3, 9, 1)
    srv.flush()
    assert n["calls"] == 2 and srv.stats.timeout_retries == 1
    assert serve_mod.POLL_SLEEP_S in sleeps
    assert 0.0 < srv.stats.readback_time_s <= srv.stats.drain_wait_s
    assert srv.result(rid) == small_index.query_batch(
        np.array([3]), np.array([9]), np.array([1]))[0]
