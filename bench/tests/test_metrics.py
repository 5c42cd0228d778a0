"""The readers: tails over all requests from their due time."""
import numpy as np

from harness.cell_run import Run

READ = {}


def reader(bench, name):
    if name not in READ:
        READ[name] = bench.reader(name)
    return READ[name]


def make_run(due, submit, deliver, seconds=10.0):
    n = len(due)
    return Run(seconds=seconds, setup_s=1.5, n=n,
               s=np.zeros(n, np.int32), t=np.zeros(n, np.int32),
               due=np.asarray(due, float), submit=np.asarray(submit, float),
               deliver=np.asarray(deliver, float),
               memo=np.zeros(n, bool), dup=np.zeros(n, bool),
               stats={"requests": n, "memo_hits": n // 4},
               host_s={"submit": 0.5, "poll": 0.25, "result": 0.25},
               row_entries=np.ones(1, np.int64), peaks=None, trace=None,
               trace_from=seconds)


def test_tail_is_over_all_requests_from_due_time(tiny_bench):
    rng = np.random.default_rng(0)
    due = np.sort(rng.random(10000) * 10)
    # the server stalls once: the 300 requests due in [5, 5.3) s all wait
    # for t = 5.5, each by its own amount
    lat = np.full(len(due), 0.002)
    stall = (due >= 5) & (due < 5.3)
    lat[stall] = 5.5 - due[stall]
    submit = due + 0.0001
    run = make_run(due, submit, due + lat)
    p99 = reader(tiny_bench, "p99_ms")(run)
    assert np.isclose(p99, np.percentile(lat * 1e3, 99,
                                        method="inverted_cdf"))
    # a median of per-second p99s would hide the stall in one chunk
    chunks = [np.percentile(lat[(due >= k) & (due < k + 1)] * 1e3, 99)
              for k in range(10)]
    assert p99 > 2 * np.median(chunks)
    # timed from due, not from submit
    late = make_run(due, due + 0.05, due + 0.05 + 0.002)
    assert np.isclose(reader(tiny_bench, "p50_ms")(late), 52.0)
    assert np.isclose(reader(tiny_bench, "gen_lag_p99_ms")(late), 50.0)


def test_a_request_never_answered_is_infinitely_late(tiny_bench):
    due = np.linspace(0, 9, 100)
    deliver = due + 0.001
    deliver[:2] = np.nan
    assert reader(tiny_bench, "p99_ms")(make_run(due, due, deliver)) \
        == np.inf


def test_front_end_readers(tiny_bench):
    run = make_run(np.zeros(1000), np.zeros(1000), np.ones(1000))
    assert np.isclose(reader(tiny_bench, "host_us_per_req.lat")(run), 1000.0)
    assert reader(tiny_bench, "memo_hit_share")(run) == 25.0
    assert reader(tiny_bench, "setup_s")(run) == 1.5
    # no trace, no device numbers
    assert reader(tiny_bench, "idle_share.lat")(run) is None
    assert reader(tiny_bench, "ragged_roofline.lat")(run) is None


def test_roofline_counts_only_the_ragged_kernel(tiny_bench):
    """Kernel time is the ops XLA names after the ragged kernel
    (``wcsd_query_ragged.<n>``), not the flush's other custom calls."""
    from harness import trace
    ev = {"devices": {"/device:TPU:0": [
        ["wcsd_query_ragged.2", 0, 1000], ["wcsd_query_ragged.3", 1000, 1000],
        ["custom-call.34", 2000, 5000], ["fusion.7", 7000, 3000]]},
        "anchor_ns": 0}
    summary = trace.reduce(ev, 0, 10_000)
    run = make_run(np.zeros(4), np.zeros(4), np.full(4, 5.0))
    run.row_entries = np.array([100, 300], np.int64)
    run.s = np.array([0, 1, 0, 1], np.int32)
    run.t = np.array([1, 1, 0, 0], np.int32)
    run.trace, run.trace_from = summary, 0.0
    run.peaks = {"hbm_bytes_per_s": 819e9}
    need = (400 + 600 + 200 + 400) * 12 / 819e9
    assert np.isclose(reader(tiny_bench, "ragged_roofline.lat")(run),
                      100.0 * need / 2000e-9)
    assert np.isclose(reader(tiny_bench, "idle_share.lat")(run), 0.0)
