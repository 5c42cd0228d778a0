"""Ragged single-launch query megakernel: the differential harness.

Paths under test: the ragged arena path (`DeviceQueryEngine(layout="csr",
dispatch="ragged")`, interpret-mode Pallas kernel AND jnp oracle, plus the
sharded engine) against the bucket-pair dispatch loop it replaced
(`dispatch="bucket_pair"`, kept as the oracle), the padded numpy outer
join, and the per-level BFS sweep — on real graphs (full (s, t, w) grids)
and on ADVERSARIAL skewed label-length distributions built directly as
synthetic CSR stores spanning several length buckets.

Also here: the launch-count regression test (ONE `pallas_call` trace per
flush shape, however many buckets the batch mixes), the plan-free-flush
guarantee (the host bucket-pair planner is never invoked on the ragged
path), the device worklist emission vs a numpy reference, and the
`resolve_interpret` resolution-table lock.
"""
import numpy as np
import pytest
from _hypo_shim import given, settings, st  # hypothesis or fallback

import jax
import jax.numpy as jnp

from repro.core.baselines import constrained_distance_grid
from repro.core.generators import erdos_renyi
from repro.core.query import (DeviceQueryEngine, ShardedQueryEngine,
                              emit_ragged_worklist, ragged_worklist_len)
from repro.core.serve import WCSDServer
from repro.core.wc_index import WCIndex, build_wc_index
from repro.kernels import ops

EXAMPLES_PER_BLOCK = 25
_instances_run = [0]


def _full_grid(V, W):
    s, t, w = np.meshgrid(np.arange(V), np.arange(V), np.arange(W + 1),
                          indexing="ij")
    return (s.ravel().astype(np.int32), t.ravel().astype(np.int32),
            w.ravel().astype(np.int32))


# ------------------------------------------------------- real-graph grids
@pytest.mark.parametrize("lane", [128, 16])
@given(st.sampled_from([8, 10, 12]), st.sampled_from([2.5, 3.5, 4.5]),
       st.sampled_from([2, 3]), st.integers(0, 100_000))
@settings(max_examples=EXAMPLES_PER_BLOCK, deadline=None, derandomize=True)
def test_ragged_agrees_with_bucket_pair_and_bfs(lane, n, deg, levels, seed):
    """Full (s, t, w) grid: ragged (kernel + jnp) == bucket-pair == BFS
    sweep, single-level AND profile. lane=16 forces multi-tile rows and
    multi-bucket stores even on tiny graphs, so the worklist emission and
    the in-kernel tile walk are exercised, not just the 1-tile fast case."""
    g = erdos_renyi(n, deg, num_levels=levels, seed=seed + 4801 * lane)
    V, W = g.num_nodes, g.num_levels
    idx = build_wc_index(g)
    s, t, wl = _full_grid(V, W)
    D = constrained_distance_grid(g)
    exp = D[s, t, wl]

    eng_k = DeviceQueryEngine(idx, layout="csr", use_pallas=True, lane=lane)
    assert eng_k.dispatch == "ragged"
    np.testing.assert_array_equal(np.asarray(eng_k.query(s, t, wl)), exp)
    eng_j = DeviceQueryEngine(idx, layout="csr", use_pallas=False, lane=lane)
    np.testing.assert_array_equal(np.asarray(eng_j.query(s, t, wl)), exp)

    oracle = DeviceQueryEngine(idx, layout="csr", use_pallas=True, lane=lane,
                               dispatch="bucket_pair")
    np.testing.assert_array_equal(np.asarray(oracle.query(s, t, wl)), exp)

    # profile staircases, every level from the one launch
    s2, t2 = np.meshgrid(np.arange(V), np.arange(V), indexing="ij")
    s2 = s2.ravel().astype(np.int32)
    t2 = t2.ravel().astype(np.int32)
    np.testing.assert_array_equal(np.asarray(eng_k.query_profile(s2, t2)),
                                  D[s2, t2, :])
    np.testing.assert_array_equal(np.asarray(oracle.query_profile(s2, t2)),
                                  D[s2, t2, :])
    _instances_run[0] += 1


# ------------------------------------------------- adversarial skew stores
def _padded_oracle(pidx):
    hub, dist, wlev, count = pidx.labels.to_padded()
    return WCIndex(order=pidx.order, rank=pidx.rank, levels=pidx.levels,
                   hub_rank=hub, dist=dist, wlev=wlev, count=count)


@given(st.integers(0, 100_000), st.sampled_from([2, 3, 4]))
@settings(max_examples=15, deadline=None, derandomize=True)
def test_ragged_adversarial_skewed_lengths(seed, buckets):
    """Skewed length mixes across up to 4 buckets: the ragged megakernel
    (kernel + jnp), the bucket-pair loop, and the padded numpy outer join
    agree exactly — single-level and profile — on batches that hit every
    (short x short / short x heavy / heavy x heavy) pair shape. The store
    builder is SHARED with benchmarks/bench_wcsd.py: the configuration
    the perf row measures is the one this block proves correct."""
    from benchmarks.bench_wcsd import make_skewed_store
    rng = np.random.default_rng(seed)
    V, W, lane = 48, 3, 8
    pidx, heavy = make_skewed_store(V=V, W=W, lane=lane, buckets=buckets,
                                    rng=rng)
    oracle = _padded_oracle(pidx)
    B = 160
    s = rng.integers(0, V, B).astype(np.int32)
    t = rng.integers(0, V, B).astype(np.int32)
    s[:buckets] = np.resize(heavy, buckets)   # force heavy x heavy pairs
    t[:buckets] = np.resize(heavy[::-1], buckets)
    wl = rng.integers(0, W + 1, B).astype(np.int32)
    exp = oracle.query_batch(s, t, wl)

    eng_k = DeviceQueryEngine(pidx, layout="csr", use_pallas=True, lane=lane)
    eng_j = DeviceQueryEngine(pidx, layout="csr", use_pallas=False, lane=lane)
    bp = DeviceQueryEngine(pidx, layout="csr", use_pallas=False, lane=lane,
                           dispatch="bucket_pair")
    np.testing.assert_array_equal(np.asarray(eng_k.query(s, t, wl)), exp)
    np.testing.assert_array_equal(np.asarray(eng_j.query(s, t, wl)), exp)
    np.testing.assert_array_equal(np.asarray(bp.query(s, t, wl)), exp)

    exp_prof = np.stack([oracle.query_batch(s, t, np.full(B, w, np.int32))
                         for w in range(W + 1)], axis=1)
    np.testing.assert_array_equal(np.asarray(eng_k.query_profile(s, t)),
                                  exp_prof)
    np.testing.assert_array_equal(np.asarray(bp.query_profile(s, t)),
                                  exp_prof)


# ----------------------------------------------------------- both engines
def test_sharded_ragged_matches_device_engine():
    """ShardedQueryEngine(dispatch="ragged") == DeviceQueryEngine bit for
    bit (1-device mesh in-process; the 8-virtual-device sweep runs in
    launch.dryrun --serve) — in BOTH placements: replicated arena, and
    the row-sharded store (which used to silently fall back to
    bucket_pair and now keeps the megakernel via the worklist tile
    gather), compressed arena included."""
    from repro.launch.mesh import make_serving_mesh
    g = erdos_renyi(40, 3.5, num_levels=3, seed=9)
    idx = build_wc_index(g)
    rng = np.random.default_rng(1)
    s = rng.integers(0, 40, 300).astype(np.int32)
    t = rng.integers(0, 40, 300).astype(np.int32)
    wl = rng.integers(0, 4, 300).astype(np.int32)
    dev = DeviceQueryEngine(idx, layout="csr", use_pallas=True)
    exp = np.asarray(dev.query(s, t, wl))
    exp_prof = np.asarray(dev.query_profile(s, t))
    sh = ShardedQueryEngine(idx, mesh=make_serving_mesh(), layout="csr",
                            use_pallas=True)
    assert sh.dispatch == "ragged"
    np.testing.assert_array_equal(np.asarray(sh.query(s, t, wl)), exp)
    np.testing.assert_array_equal(np.asarray(sh.query_profile(s, t)),
                                  exp_prof)
    # row-sharded labels keep the ragged megakernel: the flush gathers
    # each device's worklist tiles with ONE reduce-scatter
    for compressed in (False, True):
        rs = ShardedQueryEngine(idx, mesh=make_serving_mesh(), layout="csr",
                                device_budget_bytes=1, dispatch="ragged",
                                use_pallas=True, compressed=compressed)
        assert rs.mode == "sharded_labels" and rs.dispatch == "ragged"
        assert rs.compressed is compressed
        np.testing.assert_array_equal(np.asarray(rs.query(s, t, wl)), exp)
        np.testing.assert_array_equal(np.asarray(rs.query_profile(s, t)),
                                      exp_prof)


# ------------------------------------------------------------ launch count
def test_one_pallas_launch_per_flush():
    """Acceptance: a 4096-query batch mixing several length buckets is
    served by EXACTLY ONE ragged `pallas_call` trace per flush shape —
    where the bucket-pair dispatch traces one kernel per bucket pair —
    and the answers are bit-identical to the bucket-pair path and the BFS
    sweep."""
    import repro.kernels.wcsd_query as wq

    g = erdos_renyi(60, 4.0, num_levels=4, seed=77)
    idx = build_wc_index(g)
    lane = 16
    packed = idx.packed(lane=lane)
    assert packed.num_buckets >= 2, "config no longer mixes buckets"
    D = constrained_distance_grid(g)
    rng = np.random.default_rng(3)
    B = 4096
    s = rng.integers(0, g.num_nodes, B).astype(np.int32)
    t = rng.integers(0, g.num_nodes, B).astype(np.int32)
    wl = rng.integers(0, g.num_levels + 1, B).astype(np.int32)
    exp = D[s, t, wl]

    calls = []
    real = wq.pl.pallas_call

    def counting(*a, **k):
        calls.append(a)
        return real(*a, **k)

    wq.pl.pallas_call = counting
    try:
        eng = DeviceQueryEngine(idx, layout="csr", use_pallas=True,
                                lane=lane)
        got = np.asarray(eng.query(s, t, wl))
        assert len(calls) == 1, \
            f"expected ONE ragged launch per flush, traced {len(calls)}"
        # same flush shape again: the compiled call is reused, no re-trace
        got2 = np.asarray(eng.query(s, t, wl))
        assert len(calls) == 1
        # the bucket-pair loop traces one kernel per (bucket_s, bucket_t)
        calls.clear()
        bp = DeviceQueryEngine(idx, layout="csr", use_pallas=True,
                               lane=lane, dispatch="bucket_pair")
        exp_bp = np.asarray(bp.query(s, t, wl))
        n_pairs = len(
            {(packed.bucket_of[a], packed.bucket_of[b])
             for a, b in zip(s.tolist(), t.tolist())})
        assert len(calls) == n_pairs > 1
    finally:
        wq.pl.pallas_call = real
    np.testing.assert_array_equal(got, exp)
    np.testing.assert_array_equal(got2, exp)
    np.testing.assert_array_equal(exp_bp, exp)


def test_flush_over_smem_budget_splits_into_launches(monkeypatch):
    """A flush whose worklist scalars exceed the per-launch SMEM budget
    runs as several equal launches — through `DeviceQueryEngine` and
    `WCSDServer` alike — whose min-combined answers equal
    `query_batch_jnp` / `profile_batch_jnp` exactly; the launch count is
    the worklist length over the per-launch capacity, rounded up."""
    import repro.kernels.wcsd_query as wq
    from repro.core.query import profile_batch_jnp, query_batch_jnp

    g = erdos_renyi(60, 4.0, num_levels=4, seed=77)
    idx = build_wc_index(g)
    B = 256                                   # a power of two: no pad lanes
    # distinct undirected keys, so the server's memo folds none of them
    keys = np.random.default_rng(5).permutation(
        [(a, b, w) for a in range(g.num_nodes) for b in range(a, g.num_nodes)
         for w in range(g.num_levels + 1)])[:B].astype(np.int32)
    s, t, wl = keys.T
    labels = (idx.hub_rank, idx.dist, idx.wlev, idx.count)
    exp = np.asarray(query_batch_jnp(*labels, s, t, wl))
    exp_prof = np.asarray(profile_batch_jnp(*labels, s, t,
                                            num_levels=g.num_levels))
    per_launch = 48
    # four scalar arrays per work item on the uncompressed arena
    monkeypatch.setattr(wq, "_PREFETCH_BUDGET_WORDS", 4 * per_launch)
    tile_cnt = idx.packed().arena().tile_cnt
    WL = ragged_worklist_len(tile_cnt, s, t)
    want = -(-WL // per_launch)
    assert want > 1

    launches = []
    real = wq.pl.pallas_call

    def counting(*a, **k):
        launches.append(a)
        return real(*a, **k)

    monkeypatch.setattr(wq.pl, "pallas_call", counting)
    jax.clear_caches()          # re-trace under the shrunken budget
    try:
        eng = DeviceQueryEngine(idx, layout="csr", use_pallas=True)
        np.testing.assert_array_equal(np.asarray(eng.query(s, t, wl)), exp)
        assert len(launches) == want
        launches.clear()
        np.testing.assert_array_equal(eng.query_profile(s, t), exp_prof)
        assert len(launches) == want
        launches.clear()
        jax.clear_caches()      # same flush shape: trace it again
        srv = WCSDServer(idx, layout="csr", use_pallas=True, max_batch=B,
                         backend="device")
        np.testing.assert_array_equal(srv.query_many(s, t, wl), exp)
        assert srv.stats.batches == 1 and len(launches) == want
    finally:
        monkeypatch.undo()
        jax.clear_caches()      # later tests must not reuse these traces


def test_rowsharded_one_launch_one_collective_per_flush():
    """Acceptance for the ROW-SHARDED ragged path, on 8 virtual devices
    (subprocess — the device count must be fixed before jax initializes):
    a mixed-bucket flush with the label store tile-row-sharded traces
    EXACTLY ONE ragged `pallas_call` (the per-device launch is one SPMD
    trace) plus ONE `psum_scatter` (the fused worklist tile gather), a
    repeat flush traces nothing new, and the answers are bit-identical to
    the single-device engine."""
    import os
    import subprocess
    import sys

    prog = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
import repro.kernels.wcsd_query as wq
from repro.core.generators import erdos_renyi
from repro.core.query import DeviceQueryEngine, ShardedQueryEngine
from repro.core.wc_index import build_wc_index
from repro.launch.mesh import make_serving_mesh

g = erdos_renyi(60, 4.0, num_levels=4, seed=77)
idx = build_wc_index(g)
lane = 16
assert idx.packed(lane=lane).num_buckets >= 2, "config no longer mixes buckets"
rng = np.random.default_rng(3)
B = 1024
s = rng.integers(0, g.num_nodes, B).astype(np.int32)
t = rng.integers(0, g.num_nodes, B).astype(np.int32)
wl = rng.integers(0, g.num_levels + 1, B).astype(np.int32)
dev = DeviceQueryEngine(idx, layout="csr", use_pallas=True, lane=lane)
exp = np.asarray(dev.query(s, t, wl))
exp_prof = np.asarray(dev.query_profile(s, t))

pallas_traces, coll_traces = [], []
real_pc, real_ps = wq.pl.pallas_call, jax.lax.psum_scatter
def counting_pc(*a, **k):
    pallas_traces.append(a)
    return real_pc(*a, **k)
def counting_ps(*a, **k):
    coll_traces.append(a)
    return real_ps(*a, **k)
wq.pl.pallas_call = counting_pc
jax.lax.psum_scatter = counting_ps
try:
    eng = ShardedQueryEngine(idx, mesh=make_serving_mesh(), layout="csr",
                             lane=lane, use_pallas=True,
                             device_budget_bytes=1, dispatch="ragged")
    assert eng.mode == "sharded_labels" and eng.dispatch == "ragged"
    got = np.asarray(eng.query(s, t, wl))
    assert len(pallas_traces) == 1, f"{len(pallas_traces)} pallas traces"
    assert len(coll_traces) == 1, f"{len(coll_traces)} collective traces"
    # same flush shape again: compiled call reused, nothing re-traced
    got2 = np.asarray(eng.query(s, t, wl))
    assert len(pallas_traces) == 1 and len(coll_traces) == 1
    # the profile flush pays the same budget: one launch + one gather
    pallas_traces.clear(); coll_traces.clear()
    prof = np.asarray(eng.query_profile(s, t))
    assert len(pallas_traces) == 1, f"{len(pallas_traces)} pallas traces"
    assert len(coll_traces) == 1, f"{len(coll_traces)} collective traces"
finally:
    wq.pl.pallas_call = real_pc
    jax.lax.psum_scatter = real_ps
np.testing.assert_array_equal(got, exp)
np.testing.assert_array_equal(got2, exp)
np.testing.assert_array_equal(prof, exp_prof)
print("OK one launch one collective")
"""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = {**os.environ, "PYTHONPATH": src, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=420)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "OK one launch one collective" in r.stdout


def test_delta_serving_one_pallas_launch_per_flush():
    """Acceptance (dynamic serving): a flush over the main + delta arenas
    still traces EXACTLY ONE ragged `pallas_call` — the delta region is
    appended tiles in the SAME arena, its worklist items ride the same
    launch (docs/dynamic-index.md) — and the answers are bit-identical to
    the BFS sweep on the mutated graph."""
    import repro.kernels.wcsd_query as wq
    from repro.core.wc_index import DynamicWCIndex

    g = erdos_renyi(60, 4.0, num_levels=4, seed=77)
    idx = build_wc_index(g)
    lane = 16
    base_tiles = idx.packed(lane=lane).arena(lane=lane).num_tiles
    dyn = DynamicWCIndex(idx, g)
    dyn.apply_updates(
        inserts=[(0, 30, float(g.levels[1]))],
        deletes=[(int(g.edges_src[0]), int(g.edges_dst[0]))])
    assert not dyn.delta.is_empty()
    ext = dyn.packed(lane=lane).arena(lane=lane)
    assert ext.num_tiles > base_tiles, "no delta region appended"

    D = constrained_distance_grid(dyn.graph)
    rng = np.random.default_rng(3)
    B = 4096
    s = rng.integers(0, g.num_nodes, B).astype(np.int32)
    t = rng.integers(0, g.num_nodes, B).astype(np.int32)
    wl = rng.integers(0, g.num_levels + 1, B).astype(np.int32)
    exp = D[s, t, wl]

    calls = []
    real = wq.pl.pallas_call

    def counting(*a, **k):
        calls.append(a)
        return real(*a, **k)

    wq.pl.pallas_call = counting
    try:
        eng = DeviceQueryEngine(dyn, layout="csr", use_pallas=True,
                                lane=lane)
        got = np.asarray(eng.query(s, t, wl))
        assert len(calls) == 1, \
            f"expected ONE launch over main+delta, traced {len(calls)}"
        got2 = np.asarray(eng.query(s, t, wl))
        assert len(calls) == 1  # compiled call reused
    finally:
        wq.pl.pallas_call = real
    np.testing.assert_array_equal(got, exp)
    np.testing.assert_array_equal(got2, exp)


def test_rowsharded_delta_one_launch_one_collective_per_flush():
    """The row-sharded flavor of the delta launch lock, on 8 virtual
    devices (subprocess): one `pallas_call` trace + one `psum_scatter`
    trace per flush with the delta-extended arena tile-sharded over the
    mesh, answers bit-identical to the single-device dynamic engine."""
    import os
    import subprocess
    import sys

    prog = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
import repro.kernels.wcsd_query as wq
from repro.core.generators import erdos_renyi
from repro.core.query import DeviceQueryEngine, ShardedQueryEngine
from repro.core.wc_index import DynamicWCIndex, build_wc_index
from repro.launch.mesh import make_serving_mesh

g = erdos_renyi(60, 4.0, num_levels=4, seed=77)
idx = build_wc_index(g)
lane = 16
dyn = DynamicWCIndex(idx, g)
dyn.apply_updates(inserts=[(0, 30, float(g.levels[1]))],
                  deletes=[(int(g.edges_src[0]), int(g.edges_dst[0]))])
assert not dyn.delta.is_empty()
rng = np.random.default_rng(3)
B = 1024
s = rng.integers(0, g.num_nodes, B).astype(np.int32)
t = rng.integers(0, g.num_nodes, B).astype(np.int32)
wl = rng.integers(0, g.num_levels + 1, B).astype(np.int32)
dev = DeviceQueryEngine(dyn, layout="csr", use_pallas=True, lane=lane)
exp = np.asarray(dev.query(s, t, wl))

pallas_traces, coll_traces = [], []
real_pc, real_ps = wq.pl.pallas_call, jax.lax.psum_scatter
def counting_pc(*a, **k):
    pallas_traces.append(a)
    return real_pc(*a, **k)
def counting_ps(*a, **k):
    coll_traces.append(a)
    return real_ps(*a, **k)
wq.pl.pallas_call = counting_pc
jax.lax.psum_scatter = counting_ps
try:
    eng = ShardedQueryEngine(dyn, mesh=make_serving_mesh(), layout="csr",
                             lane=lane, use_pallas=True,
                             device_budget_bytes=1, dispatch="ragged")
    assert eng.mode == "sharded_labels" and eng.dispatch == "ragged"
    got = np.asarray(eng.query(s, t, wl))
    assert len(pallas_traces) == 1, f"{len(pallas_traces)} pallas traces"
    assert len(coll_traces) == 1, f"{len(coll_traces)} collective traces"
    got2 = np.asarray(eng.query(s, t, wl))
    assert len(pallas_traces) == 1 and len(coll_traces) == 1
finally:
    wq.pl.pallas_call = real_pc
    jax.lax.psum_scatter = real_ps
np.testing.assert_array_equal(got, exp)
np.testing.assert_array_equal(got2, exp)
print("OK delta one launch one collective")
"""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = {**os.environ, "PYTHONPATH": src, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=420)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "OK delta one launch one collective" in r.stdout


def test_ragged_flush_never_calls_host_planner(monkeypatch):
    """The ragged path's batch plan is emitted on device: the host
    bucket-pair planner must not run on any flush (that is what makes
    `WCSDServer.flush_async` plan-free)."""
    import repro.core.query as q

    def boom(*a, **k):
        raise AssertionError("host planner invoked on the ragged path")

    monkeypatch.setattr(q, "plan_query_batch", boom)
    g = erdos_renyi(30, 3.0, num_levels=3, seed=4)
    idx = build_wc_index(g)
    srv = WCSDServer(idx, max_batch=32, layout="csr")
    rng = np.random.default_rng(0)
    s = rng.integers(0, 30, 100).astype(np.int32)
    t = rng.integers(0, 30, 100).astype(np.int32)
    wl = rng.integers(0, 3, 100).astype(np.int32)
    got = srv.query_many(s, t, wl)
    np.testing.assert_array_equal(got, idx.query_batch(s, t, wl))
    np.testing.assert_array_equal(srv.query_profile_many(s[:20], t[:20]),
                                  np.stack([idx.query_batch(
                                      s[:20], t[:20],
                                      np.full(20, w, np.int32))
                                      for w in range(4)], axis=1))


# ------------------------------------------------------- worklist emission
def test_emit_ragged_worklist_matches_numpy_reference():
    rng = np.random.default_rng(11)
    V = 20
    tile_cnt = rng.integers(1, 5, V).astype(np.int32)
    tile_base = np.zeros(V, dtype=np.int32)
    np.cumsum(tile_cnt[:-1], out=tile_base[1:])
    Q = 16
    s = rng.integers(0, V, Q).astype(np.int32)
    t = rng.integers(0, V, Q).astype(np.int32)
    total = int((tile_cnt[s].astype(np.int64) * tile_cnt[t]).sum())
    WL = ragged_worklist_len(tile_cnt, s, t)
    assert WL >= total and WL & (WL - 1) == 0

    qidx, stile, ttile = (np.asarray(a) for a in emit_ragged_worklist(
        jnp.asarray(tile_base), jnp.asarray(tile_cnt),
        jnp.asarray(s), jnp.asarray(t), worklist_len=WL))
    # numpy reference: query-major expansion of every tile pair
    c = (tile_cnt[s].astype(np.int64) * tile_cnt[t])
    exp_q = np.repeat(np.arange(Q), c)
    local = np.arange(total) - np.repeat(np.cumsum(c) - c, c)
    exp_s = tile_base[s[exp_q]] + local // tile_cnt[t[exp_q]]
    exp_t = tile_base[t[exp_q]] + local % tile_cnt[t[exp_q]]
    np.testing.assert_array_equal(qidx[:total], exp_q)
    np.testing.assert_array_equal(stile[:total], exp_s)
    np.testing.assert_array_equal(ttile[:total], exp_t)
    # pads: trash row Q, tile 0
    assert np.all(qidx[total:] == Q)
    assert np.all(stile[total:] == 0) and np.all(ttile[total:] == 0)
    # query-major: each query's work items are consecutive
    assert np.all(np.diff(qidx.astype(np.int64)) >= 0)


def test_ragged_empty_and_identity_edge_cases():
    g = erdos_renyi(10, 2.0, num_levels=2, seed=2)
    idx = build_wc_index(g)
    eng = DeviceQueryEngine(idx, layout="csr", use_pallas=True)
    empty = np.array([], dtype=np.int32)
    assert len(np.asarray(eng.query(empty, empty, empty))) == 0
    assert eng.query_profile(empty, empty).shape == (0, 3)
    v = np.arange(10, dtype=np.int32)
    # s == t is 0 at EVERY level, including the infeasible one (self entry)
    for w in range(3):
        np.testing.assert_array_equal(
            np.asarray(eng.query(v, v, np.full(10, w, np.int32))), 0)


def test_ragged_batch_pads_use_minimal_tile_vertex():
    """Batch-pad lanes must point at a minimal-tile-count vertex: padding
    with vertex 0 would cost tile_cnt[0]^2 worklist items PER PAD LANE
    whenever vertex 0 happens to be hub-heavy."""
    from benchmarks.bench_wcsd import make_skewed_store
    pidx, heavy = make_skewed_store(V=32, W=3, lane=8, buckets=3,
                                    rng=np.random.default_rng(0))
    eng = DeviceQueryEngine(pidx, layout="csr", lane=8)
    assert int(eng._tile_cnt_np[eng._pad_vertex]) == \
        int(eng._tile_cnt_np.min()) == 1
    # a 3-query batch pads to 4: the pad lane carries the cheap vertex
    h = np.resize(heavy, 3).astype(np.int32)
    stq = eng._stage_ragged(h, h, np.zeros(3, np.int32))
    assert stq.shape[1] == 4
    assert stq[0, 3] == stq[1, 3] == eng._pad_vertex


# ------------------------------------------------------ interpret default
@pytest.mark.parametrize("arg,backend,want", [
    (True, "cpu", True), (True, "tpu", True),
    (False, "cpu", False), (False, "tpu", False),
    (None, "cpu", True), (None, "gpu", True), (None, "tpu", False),
])
def test_resolve_interpret_table(monkeypatch, arg, backend, want):
    """The ONE resolution point for the interpret flag: explicit values are
    honored; None means compiled kernels exactly on TPU (the only backend
    that lowers these Mosaic kernels) and interpret emulation elsewhere —
    including GPU, where pltpu scalar prefetch cannot compile."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.resolve_interpret(arg) is want


def test_engines_resolve_interpret_through_ops(monkeypatch):
    """use_pallas=True engines (and the server) default to COMPILED kernels
    on TPU — interpret only when explicitly requested or the backend
    cannot lower Mosaic. The engine must consume the resolved bool, not
    the raw None."""
    g = erdos_renyi(12, 2.5, num_levels=2, seed=6)
    idx = build_wc_index(g)
    # this test host is CPU: None resolves to interpret=True
    assert DeviceQueryEngine(idx, use_pallas=True).interpret is True
    assert DeviceQueryEngine(idx, use_pallas=True,
                             interpret=False).interpret is False
    srv = WCSDServer(idx, layout="csr", use_pallas=True)
    assert srv.engine.interpret is True
    # on an accelerator backend the same default resolves to compiled
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert DeviceQueryEngine(idx, use_pallas=True).interpret is False
    assert DeviceQueryEngine(idx, use_pallas=True,
                             interpret=True).interpret is True


def test_rowsharded_engine_resolves_interpret_once_through_ops(monkeypatch):
    """The sharded engine resolves the interpret flag EXACTLY ONCE, at
    construction, through `kernels.ops.resolve_interpret` — and the
    row-sharded ragged flush consumes that resolved bool (it used to
    bypass the kernels entirely on the jnp fallback, so neither
    `interpret` nor `use_pallas` reached the flush). Locked in both
    placements; the resolution TABLE itself is locked by
    `test_resolve_interpret_table`."""
    from repro.launch.mesh import make_serving_mesh
    g = erdos_renyi(12, 2.5, num_levels=2, seed=6)
    idx = build_wc_index(g)
    calls = []
    real = ops.resolve_interpret

    def counting(arg):
        calls.append(arg)
        return real(arg)

    monkeypatch.setattr(ops, "resolve_interpret", counting)
    for budget in (None, 1):
        calls.clear()
        eng = ShardedQueryEngine(idx, mesh=make_serving_mesh(),
                                 layout="csr", dispatch="ragged",
                                 use_pallas=True, device_budget_bytes=budget)
        assert calls == [None], f"resolved {len(calls)}x at construction"
        assert eng.interpret is True        # CPU test host: None -> True
        v = np.arange(12, dtype=np.int32)
        np.testing.assert_array_equal(
            np.asarray(eng.query(v, v, np.zeros(12, np.int32))), 0)
        assert calls == [None], "flush re-resolved the interpret flag"


def test_ragged_harness_coverage_target():
    """>= 50 generated real-graph instances (2 lane blocks x 25) plus the
    adversarial-skew block; when blocks ran in this session each produced
    its full example count (no silent early exits)."""
    assert 2 * EXAMPLES_PER_BLOCK >= 50
    if _instances_run[0]:
        assert _instances_run[0] % EXAMPLES_PER_BLOCK == 0
