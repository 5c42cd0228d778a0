"""Profile (staircase) requests in a traffic mix: drawn beside the point
requests without moving their draws, driven, warmed, deduplicated and
checked at every level; the readers and the point checks unchanged on
point-only runs."""
import hashlib
import json
import os
import re
import shutil
import time

import numpy as np
import pytest

from conftest import BENCH, CHECKOUT, TINY_ROAD, TINY_SOCIAL
from harness import drive, graphs
from harness.check import duplicates, judge, sample
from harness.reference import UNREACHABLE, Reference
from harness.traffic import (POINT, PROFILE, PairSource, open_schedule,
                             rng_for)

BIG_SEED = 2**31 + 12345
SEEDS = (7, BIG_SEED)


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def real_mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------- the parent's point draws
# Digests taken from the harness before profile requests were added; a mix
# without profiles has to draw, warm, sample and check exactly as it did.
GRAPH = {"open-uniform": (10000, 5), "open-zipf": (10000, 9)}
PARENT = {
    ("window", "open-uniform", 7):
        "bca95f9f8ab0a316711b3dcaf6644b46daf0f995bd2f606470481151553d6faa",
    ("warmup", "open-uniform", 7):
        "7e4ab4376871b124eaffb0e837463201064bb718caae8363366bdaf9197b3a88",
    ("window", "open-uniform", BIG_SEED):
        "5b48970138c801bbc777c0b906d387ae44e54faa0195136f0565335d69e0d626",
    ("warmup", "open-uniform", BIG_SEED):
        "41a8cfe1c367ce5d9d6d1a423a6a3dcde2ee327e75d5bb89c60104863806ac69",
    ("window", "open-zipf", 7):
        "40e3547adcf76a37e2eda81a0e527d2c5571d4cebb657d29e60fc679805fd1c4",
    ("warmup", "open-zipf", 7):
        "6bbdfa98f8706ea5e69ddd6fcf3b4b00492097100fcced7b1f9e1732751fc2a3",
    ("window", "open-zipf", BIG_SEED):
        "db08b34c39f9c2c4e19a906f032d519a25e0eb1652b6a31d87f0974db4df049b",
    ("warmup", "open-zipf", BIG_SEED):
        "57dd9d049fa0bf012fdd79f74e6de86becf478302160ca07809e762df813e897",
    ("sample", None, 7):
        "b405c29f47ad8791ad78ea6d3e5e56275815238e20804ea9dc90875b64065730",
    ("sample", None, BIG_SEED):
        "c0f58dc6f51cc996127d4dabdc72c75bd14b69046f10ec3b40e63905cef6493a",
    ("checks", None, 7):
        "c326e5f2cc4739692057560ea74be2e9e7d11cbdb4e1a2e61af66f399fa65a89",
    ("checks", None, BIG_SEED):
        "7c0c5a4d70493be669dec77aa604451b13d52bd2a2b3a39235ab59ac78a5aa84",
    ("control", None, 7):
        "0533cc2bcf1364c9c1efb26c48e157db8e183f977c3f88307323a712061a10a4",
    ("control", None, BIG_SEED):
        "b69d480be5c04fac342e36851f839448dd88fb98c24ba8bd424b3b72964ccc58",
}


class RecordingServer:
    """Stands in for a WCSDServer under `drive.warm_shapes`: records each
    submit and flush. It has no profile calls, so a point-only warm-up
    that reached for one would fail here."""

    def __init__(self, max_batch):
        self.max_batch = max_batch
        self.max_wait_us = 500.0
        self.memo = {}
        self.sent = []

    def submit(self, s, t, w):
        self.sent.append((s, t, w))
        return len(self.sent) - 1

    def flush(self):
        self.sent.append((-1, -1, -1))

    def result(self, rid):
        return 0


def window_draws(name, seed):
    mix = real_mix(name)
    V, W = GRAPH[name]
    rng = rng_for(seed, "window")
    due = open_schedule(mix, 2.0, rng)
    return digest(due, *PairSource(mix, V, W, seed).draw(rng, len(due)))


def warmup_draws(name, seed):
    """warm_shapes' submits and flushes, then one slice of traffic."""
    mix = real_mix(name)
    V, W = GRAPH[name]
    src = PairSource(mix, V, W, seed)
    rng = rng_for(seed, "warmup")
    tile_cnt = 1 + np.random.default_rng(0).geometric(0.3, V)
    srv = RecordingServer(256)
    sent = drive.warm_shapes(srv, src, rng, tile_cnt)
    due = open_schedule(mix, 0.5, rng)
    return digest(np.array(srv.sent), np.array([sent]), due,
                  *src.draw(rng, len(due)))


def sample_draws(seed):
    r = np.random.default_rng(3)
    kind = r.integers(0, 3, 50000)
    origin = {k: np.flatnonzero(kind == i)
              for i, k in enumerate(("device", "memo", "dup"))}
    return digest(sample(origin, r.integers(1, 1000, 50000), seed))


def point_checks(seed):
    """judge's checks on a point-only window with planted errors."""
    ref = Reference(graphs.make_graph(TINY_ROAD))
    mix = {"rate_per_s": 400, "pairs": "uniform", "levels": "uniform"}
    rng = rng_for(seed, "window")
    due = open_schedule(mix, 5.0, rng)
    req = drive.Requests.empty(*PairSource(mix, ref.V, ref.num_levels,
                                           seed).draw(rng, len(due)))
    n = req.n = len(due)
    req.due = req.submit = due
    req.deliver = due + 0.02
    req.deliver[::53] = np.nan
    req.answer = ref.distances(req.s, req.t, req.w)
    req.answer[::41] += 1
    req.mode[::5] = 1
    req.mode[::61] = 2
    dup = duplicates(req.s, req.t, req.w, req.submit, req.deliver, ref.V,
                     ref.num_levels)
    checks, counts = judge(req, n, ref, dup, np.arange(n), seed,
                           {"mode": "primary", "retries": 0, "demotions": 1})
    return json.dumps([[c.name, c.as_json()] for c in checks] + [counts])


def control_checks(seed):
    import control
    from harness.spec import Bench
    out = control.control_run(Bench(CHECKOUT).cell("road-uniform"), seed, 0.5)
    return json.dumps(out["checks"])


def read_parent(what, name, seed):
    if what == "window":
        return window_draws(name, seed)
    if what == "warmup":
        return warmup_draws(name, seed)
    if what == "sample":
        return sample_draws(seed)
    text = point_checks(seed) if what == "checks" else control_checks(seed)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("what,name,seed", sorted(PARENT, key=str),
                         ids=lambda x: str(x))
def test_point_only_mixes_match_the_parent(what, name, seed):
    assert read_parent(what, name, seed) == PARENT[(what, name, seed)]


# ------------------------------------------------------------ the draws
def test_profile_share_keeps_the_point_draws():
    """A share of profiles changes no arrival, pair or level: only which
    requests are profiles, exactly round(share * n) of them, from the
    kind stream."""
    mix = real_mix("open-uniform")
    rng = rng_for(BIG_SEED, "window")
    due = open_schedule(mix, 1.0, rng)
    plain = PairSource(mix, 1000, 5, BIG_SEED)
    a = plain.draw(rng, len(due))
    assert plain.kinds(None, len(due)) is None
    mixed = dict(mix, profile_share=0.25)
    rng = rng_for(BIG_SEED, "window")
    due2 = open_schedule(mixed, 1.0, rng)
    src = PairSource(mixed, 1000, 5, BIG_SEED)
    b = src.draw(rng, len(due2))
    np.testing.assert_array_equal(due, due2)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    kind = src.kinds(rng_for(BIG_SEED, "kind", "window"), len(due))
    assert kind.dtype == np.int8
    assert int((kind == PROFILE).sum()) == round(0.25 * len(due))
    np.testing.assert_array_equal(
        kind, src.kinds(rng_for(BIG_SEED, "kind", "window"), len(due)))
    assert not np.array_equal(
        kind, src.kinds(rng_for(BIG_SEED, "kind", "warmup"), len(due)))
    with pytest.raises(ValueError):
        src.kinds(None, 10)
    with pytest.raises(ValueError):
        PairSource(dict(mix, profile_share=1.5), 1000, 5, 1)


def test_duplicates_key_profiles_by_pair():
    """A profile rides an open profile of its pair, whatever the level
    drawn; never a point request, and no point request rides it."""
    s = np.array([1, 2, 1, 1, 3])
    t = np.array([2, 1, 2, 2, 4])
    w = np.array([0, 1, 0, 1, 0])
    kind = np.array([PROFILE, PROFILE, POINT, POINT, PROFILE], np.int8)
    submit = np.array([0.0, 1.0, 2.0, 2.5, 3.0])
    deliver = np.array([5.0, 5.0, 6.0, 6.0, 6.0])
    np.testing.assert_array_equal(
        duplicates(s, t, w, submit, deliver, 10, 2, kind),
        [False, True, False, False, False])
    kind[3] = PROFILE     # a profile of pair (1, 2) while 0 is still open
    assert duplicates(s, t, w, submit, deliver, 10, 2, kind)[3]


def test_staircases_are_checked_at_every_level():
    """judge compares each sampled staircase with the reference at levels
    0..W; level W is 0 on the diagonal and unreachable elsewhere."""
    edges = graphs.make_graph(TINY_ROAD)
    ref = Reference(edges)
    W = ref.num_levels
    mix = {"rate_per_s": 400, "pairs": "uniform", "levels": "uniform",
           "profile_share": 0.5}
    src = PairSource(mix, ref.V, W, 11)
    rng = rng_for(11, "window")
    due = open_schedule(mix, 2.0, rng)
    n = len(due)
    req = drive.Requests.empty(*src.draw(rng, n),
                               src.kinds(rng_for(11, "kind", "window"), n), W)
    diag = np.flatnonzero(req.kind == PROFILE)[:3]
    req.s[diag] = req.t[diag] = 5      # a few staircases with s == t
    req.n = n
    req.due = req.submit = due
    req.deliver = due + 1e-3
    point = req.kind == POINT
    req.answer[point] = ref.distances(req.s[point], req.t[point],
                                      req.w[point])
    for i in np.flatnonzero(~point):
        req.profile[i] = ref.distances(np.full(W + 1, req.s[i]),
                                       np.full(W + 1, req.t[i]),
                                       np.arange(W + 1))
    assert req.profile.shape == (n, W + 1)
    diag = req.s == req.t
    assert (~point & diag).sum() >= 3
    np.testing.assert_array_equal(req.profile[~point & diag, W], 0)
    assert (req.profile[~point & ~diag, W] == UNREACHABLE).all()
    server = {"mode": "primary", "retries": 0, "demotions": 0}

    def verdict():
        dup = duplicates(req.s, req.t, req.w, req.submit, req.deliver,
                         ref.V, W, req.kind)
        checks, counts = judge(req, n, ref, dup, np.ones(n, np.int64), 11,
                               server)
        return {c.name: c for c in checks}, counts

    checks, counts = verdict()
    assert all(c.ok for c in checks.values()), checks
    assert checks["profile_checked"].value == int((~point).sum())
    assert checks["checked"].value == int(point.sum())
    assert counts["profile_device"] > 0
    # one level of one staircase off: caught by the profile check alone
    k = np.flatnonzero(~point)[7]
    req.profile[k, W] += 1
    checks, _ = verdict()
    assert checks["profile_wrong"].value == 1 and checks["wrong"].value == 0
    assert not checks["profile_wrong"].ok
    req.profile[k, W] -= 1
    # a staircase never delivered is lost
    req.deliver[k] = np.nan
    checks, _ = verdict()
    assert checks["lost"].value == 1


# ------------------------------------------------------- tiny mixed cells
MIXED = {
    "uniform-mix": {"rate_per_s": 400, "pairs": "uniform",
                    "levels": "uniform", "profile_share": 0.5},
    "zipf-mix": {"rate_per_s": 400, "pairs": "zipf", "zipf_a": 1.0,
                 "levels": "uniform", "profile_share": 0.5},
}
MIXED_CELLS = [("tiny-road-mix", "tiny-road", "uniform-mix"),
               ("tiny-social-mix", "tiny-social", "zipf-mix")]


@pytest.fixture(scope="module")
def mix_bench(tmp_path_factory):
    """Tiny cells with half of their requests profiles, in a benchmark
    directory of their own, with the real readers and peaks."""
    from harness.spec import Bench
    root = tmp_path_factory.mktemp("mixbench")
    for sub in ("configs", "traffic"):
        (root / sub).mkdir()
    shutil.copytree(os.path.join(BENCH, "metrics"), root / "metrics")
    shutil.copy(os.path.join(BENCH, "peaks.json"), root / "peaks.json")
    for cfg in (TINY_ROAD, TINY_SOCIAL):
        (root / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    for name, mix in MIXED.items():
        (root / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        real = json.load(f)
    names = sorted(n for n, _, _ in MIXED_CELLS)

    def tiny(m):
        return dict(m, workloads=names) if "workloads" in m else m

    spec = {
        "configs": [{"name": c["name"],
                     "file": str(root / "configs" / f"{c['name']}.json")}
                    for c in (TINY_ROAD, TINY_SOCIAL)],
        "workloads": [{"name": n, "config": c, "traffic": m, "chips": 1}
                      for n, c, m in MIXED_CELLS],
        "end_to_end": [tiny(m) for m in real["end_to_end"]],
        "per_layer": [tiny(m) for m in real["per_layer"]],
    }
    return Bench(CHECKOUT, bench_dir=str(root), spec=spec)


def run_mixed(bench, name, seed=2**31 + 77, traced=False):
    import jax

    from harness.cell_run import run_cell
    cell = bench.cell(name)
    out, _ = run_cell(bench, cell, seed, 1.0, traced, time.perf_counter(),
                      jax.devices()[:1], "cpu")
    json.dumps(out)
    return out


@pytest.mark.parametrize("name", [n for n, _, _ in MIXED_CELLS])
def test_mixed_run_is_correct_and_warm(mix_bench, quick, capsys, name):
    out = run_mixed(mix_bench, name)
    err = capsys.readouterr().err
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["profile_checked"]["value"] > 0
    assert out["checks"]["profile_wrong"]["value"] == 0
    assert list(out)[-1] == "checks"
    lowered = re.search(r"programs lowered in the window (\d+), compiled "
                        r"(\d+)", err)
    assert lowered and lowered.groups() == ("0", "0"), err


def test_traced_mixed_run_reads_no_new_program(mix_bench, quick):
    out = run_mixed(mix_bench, "tiny-road-mix", traced=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["new_programs"]["value"] == 0.0


def patch_profiles(monkeypatch, spoil):
    """Spoil every profile flush's staircases where the engine makes them."""
    from repro.core import query
    orig = query.ShardedQueryEngine.query_profile_async

    def query_profile_async(self, s, t):
        handle = orig(self, s, t)
        finalize = handle._finalize
        handle._finalize = lambda: spoil(np.array(finalize()))
        return handle

    monkeypatch.setattr(query.ShardedQueryEngine, "query_profile_async",
                        query_profile_async)


def shifted_one_level(out):
    out[:, 1:] = out[:, :-1].copy()
    return out


def test_staircase_shifted_by_one_level_is_caught(mix_bench, quick,
                                                  monkeypatch):
    patch_profiles(monkeypatch, shifted_one_level)
    out = run_mixed(mix_bench, "tiny-road-mix")
    assert not out["correct"]
    assert out["checks"]["profile_wrong"]["value"] > 0


def test_dropped_profiles_are_lost(mix_bench, quick, monkeypatch):
    """The server hands out an id for every 10th profile and drops it."""
    from repro.core.serve import WCSDServer
    orig = WCSDServer.submit_profile

    def submit_profile(self, s, t):
        if self._next_rid % 10 == 9:
            self._next_rid += 1
            return self._next_rid - 1
        return orig(self, s, t)

    monkeypatch.setattr(WCSDServer, "submit_profile", submit_profile)
    monkeypatch.setattr(drive, "warm_shapes", lambda *a: 0)
    monkeypatch.setattr(drive, "GRACE_S", 0.5)
    out = run_mixed(mix_bench, "tiny-social-mix")
    assert not out["correct"]
    assert out["checks"]["lost"]["value"] == out["failed"] > 0


def test_points_right_profiles_wrong_is_caught(mix_bench, quick, monkeypatch):
    """Staircases spoiled on their way to the client only: every point
    answer (those the memo reads from a staircase too) is right, and the
    profile check alone reads the fault."""
    from repro.core.serve import WCSDServer
    orig = WCSDServer.profile_result_with_mode

    def spoiled(self, rid):
        value, mode = orig(self, rid)
        return value + 1, mode

    monkeypatch.setattr(WCSDServer, "profile_result_with_mode", spoiled)
    out = run_mixed(mix_bench, "tiny-road-mix")
    assert not out["correct"]
    assert out["checks"]["wrong"]["value"] == 0
    assert out["checks"]["profile_wrong"]["value"] > 0


@pytest.mark.parametrize("name", [n for n, _, _ in MIXED_CELLS])
def test_control_reads_false_on_a_mixed_mix(mix_bench, name):
    import control
    out = control.control_run(mix_bench.cell(name), BIG_SEED, 2.0)
    assert not out["correct"]
    assert out["checks"]["profile_wrong"]["value"] > 0
    assert out["checks"]["wrong"]["value"] > 0
    assert out["checks"]["lost"]["value"] == 0


# --------------------------------------------------------------- readers
READERS = sorted(f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics"))
                 if f.endswith(".py"))


def point_run():
    """A point-only run with every input a reader takes: a trace of the
    ragged kernel, peaks, memo hits, duplicates and server counters."""
    from harness import trace
    from test_metrics import make_run
    rng = np.random.default_rng(5)
    n = 400
    due = np.sort(rng.random(n) * 10)
    run = make_run(due, due + 1e-4, due + rng.random(n) * 0.01)
    run.memo = rng.random(n) < 0.2
    run.dup = ~run.memo & (rng.random(n) < 0.1)
    run.s = rng.integers(0, 50, n).astype(np.int32)
    run.t = rng.integers(0, 50, n).astype(np.int32)
    run.row_entries = rng.integers(10, 900, 50).astype(np.int64)
    run.peaks = {"hbm_bytes_per_s": 819e9}
    run.trace = trace.reduce({"devices": {"/device:TPU:0": [
        ["wcsd_query_ragged.2", 0, 2e9], ["fusion.7", 2e9, 1e9]]},
        "anchor_ns": 0}, 0, 10e9)
    run.trace_from = 0.0
    run.stats.update(profile_requests=0, dispatch_time_s=0.004,
                     deliver_time_s=0.001, delivered=n, batches=40,
                     readback_time_s=0.01, new_programs=0)
    return run


@pytest.mark.parametrize("name", READERS)
def test_readers_unchanged_by_the_kind_field(tiny_bench, name):
    from test_metrics import reader
    read = reader(tiny_bench, name)
    run = point_run()
    assert run.kind is None
    before = read(run)
    run.kind = np.zeros(run.n, np.int8)
    assert read(run) == before
    assert name in ("idle_share.lat", "ragged_roofline.lat") or \
        before is not None


def test_readers_count_profiles_apart(tiny_bench):
    """Profile requests join the memo share's and the dispatch cost's
    denominators, and leave the ragged kernel's roofline."""
    from test_metrics import reader
    run = point_run()
    run.kind = np.zeros(run.n, np.int8)
    roof = reader(tiny_bench, "ragged_roofline.lat")
    before = roof(run)
    run.kind[::2] = PROFILE
    assert 0 < roof(run) < before
    run.stats.update(requests=300, profile_requests=100, memo_hits=100)
    assert reader(tiny_bench, "memo_hit_share")(run) == 25.0
    assert np.isclose(reader(tiny_bench, "dispatch_us_per_req")(run),
                      1e6 * 0.004 / 300)


# ------------------------------------------------------------ the graphs
PARENT_GRAPHS = {
    "road-ny-10k":
        "a738097ca2df62d096c01a823cd1969e8611af23dbc005c77f9da88982153b73",
    "social-so-10k":
        "730ecffb94fc8d4270c6dd5b49c66f3cf5250a1a0573d89ba7138ac876af55a5",
}


@pytest.mark.parametrize("name", sorted(PARENT_GRAPHS))
def test_built_in_generators_make_the_parents_graphs(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        e = graphs.make_graph(json.load(f))
    assert e.num_nodes == 10000
    assert digest(e.u, e.v, e.qual) == PARENT_GRAPHS[name]

