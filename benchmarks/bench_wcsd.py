"""Paper-table benchmarks for WCSD (Figs. 5-12, laptop-scale graphs).

One function per figure family; each prints CSV rows
``table,dataset,algo,metric,value`` and returns them as dicts. Graphs are
synthetic analogues of the paper's datasets (road grids / scale-free BA),
sized for CPU CI; the trends under test are the paper's claims, not the
absolute numbers.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.baselines import (LCRAdapt, NaiveIndex, WBFS, cbfs_query,
                                  dijkstra_query)
from repro.core.generators import random_queries, road_grid, scale_free
from repro.core.query import DeviceQueryEngine
from repro.core.serve import WCSDServer
from repro.core.wc_index import build_wc_index
from repro.core.wc_index_batched import build_wc_index_batched, clean_index

ROAD = {
    "NY(s)": dict(rows=28, cols=28, levels=5),
    "FLA(s)": dict(rows=45, cols=45, levels=5),
    "CAL(s)": dict(rows=60, cols=60, levels=5),
}
SOCIAL = {
    "MV(s)": dict(n=1500, m=4, levels=5),
    "EU(s)": dict(n=3000, m=5, levels=3),
    "SO(s)": dict(n=5000, m=4, levels=9),
}


def _road(name):
    c = ROAD[name]
    return road_grid(c["rows"], c["cols"], num_levels=c["levels"], seed=42)


def _social(name):
    c = SOCIAL[name]
    return scale_free(c["n"], c["m"], num_levels=c["levels"], seed=42)


def _time(fn, *a, repeat=1, **k):
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*a, **k)
    return (time.perf_counter() - t0) / repeat, out


def bench_indexing(datasets=None, order="auto"):
    """Fig. 5/6 analogue: indexing time + size for Naive / WC-INDEX /
    WC-INDEX+ (= query-efficient + hybrid order) / batched builder."""
    rows = []
    datasets = datasets or {**{k: ("road", k) for k in ROAD},
                            **{k: ("social", k) for k in SOCIAL}}
    for name, (fam, key) in datasets.items():
        g = _road(key) if fam == "road" else _social(key)
        o_basic = "treedec" if fam == "road" else "degree"
        t_naive, naive = _time(NaiveIndex.build, g)
        t_wc, wc = _time(build_wc_index, g, ordering=o_basic, prune=False)
        t_wcp, wcp = _time(build_wc_index, g, ordering="hybrid")
        t_bat, (bat, stats) = _time(build_wc_index_batched, g,
                                    ordering="hybrid", batch_size=32)
        rows += [
            dict(table="fig5_idx_time", dataset=name, algo="naive",
                 value=t_naive),
            dict(table="fig5_idx_time", dataset=name, algo="wc-index",
                 value=t_wc),
            dict(table="fig5_idx_time", dataset=name, algo="wc-index+",
                 value=t_wcp),
            dict(table="fig5_idx_time", dataset=name, algo="wc-batched",
                 value=t_bat),
            dict(table="fig6_idx_size", dataset=name, algo="naive",
                 value=naive.memory_bytes()),
            dict(table="fig6_idx_size", dataset=name, algo="wc-index",
                 value=wc.memory_bytes()),
            dict(table="fig6_idx_size", dataset=name, algo="wc-index+",
                 value=wcp.memory_bytes()),
            dict(table="fig6_idx_size", dataset=name, algo="wc-batched",
                 value=bat.memory_bytes()),
            dict(table="fig6_idx_size", dataset=name, algo="graph",
                 value=g.memory_bytes()),
        ]
    return rows


def bench_query(datasets=None, n_queries=400):
    """Fig. 7/12 analogue: per-query latency for online baselines vs index."""
    rows = []
    datasets = datasets or {"CAL(s)": ("road", "CAL(s)"),
                            "EU(s)": ("social", "EU(s)")}
    for name, (fam, key) in datasets.items():
        g = _road(key) if fam == "road" else _social(key)
        s, t, wl = random_queries(g, n_queries, seed=3)
        idx = build_wc_index(g, ordering="hybrid")
        naive = NaiveIndex.build(g)
        wbfs = WBFS.build(g)
        lcr = LCRAdapt.build(g)
        nq = min(60, n_queries)

        t_cbfs, _ = _time(lambda: [cbfs_query(g, int(a), int(b), int(w))
                                   for a, b, w in zip(s[:nq], t[:nq],
                                                      wl[:nq])])
        t_wbfs, _ = _time(lambda: [wbfs.query(int(a), int(b), int(w))
                                   for a, b, w in zip(s[:nq], t[:nq],
                                                      wl[:nq])])
        t_dij, _ = _time(lambda: [dijkstra_query(g, int(a), int(b), int(w))
                                  for a, b, w in zip(s[:nq], t[:nq],
                                                     wl[:nq])])
        t_lcr, _ = _time(lambda: [lcr.query(int(a), int(b), int(w))
                                  for a, b, w in zip(s[:nq], t[:nq],
                                                     wl[:nq])])
        t_nv, _ = _time(lambda: [naive.query(int(a), int(b), int(w))
                                 for a, b, w in zip(s, t, wl)])
        t_wc, _ = _time(lambda: [idx.query_one(int(a), int(b), int(w))
                                 for a, b, w in zip(s, t, wl)])
        # WC-INDEX+ device-batched path (jnp); measured per query
        eng = DeviceQueryEngine(idx)
        eng.query(s[:8], t[:8], wl[:8])  # warmup compile
        t_dev, _ = _time(lambda: np.asarray(eng.query(s, t, wl)))
        for algo, tt, n in [("c-bfs", t_cbfs, nq), ("w-bfs", t_wbfs, nq),
                            ("dijkstra", t_dij, nq), ("lcr-adapt", t_lcr, nq),
                            ("naive", t_nv, n_queries),
                            ("wc-index", t_wc, n_queries),
                            ("wc-index+dev", t_dev, n_queries)]:
            rows.append(dict(table="fig7_query_time", dataset=name,
                             algo=algo, value=tt / n))
    return rows


def bench_large_w(n_levels=20):
    """Fig. 8/9 analogue: |w| = 20."""
    rows = []
    g = road_grid(40, 40, num_levels=n_levels, seed=7)
    t_naive, naive = _time(NaiveIndex.build, g)
    t_wcp, wcp = _time(build_wc_index, g, ordering="hybrid")
    rows += [
        dict(table="fig8_w20_time", dataset="ROAD40", algo="naive",
             value=t_naive),
        dict(table="fig8_w20_time", dataset="ROAD40", algo="wc-index+",
             value=t_wcp),
        dict(table="fig9_w20_size", dataset="ROAD40", algo="naive",
             value=naive.memory_bytes()),
        dict(table="fig9_w20_size", dataset="ROAD40", algo="wc-index+",
             value=wcp.memory_bytes()),
    ]
    return rows


def bench_batched_builder():
    """Beyond-paper: PSL-style rank-batched construction — host-sync rounds
    vs sequential roots, and the index-size/cleaning trade."""
    rows = []
    g = scale_free(2000, 4, num_levels=5, seed=11)
    t_seq, seq = _time(build_wc_index, g, ordering="degree")
    for B in [8, 32, 128]:
        t_bat, (bat, stats) = _time(build_wc_index_batched, g,
                                    ordering="degree", batch_size=B)
        t_clean, (cleaned, removed) = _time(clean_index, bat)
        rows += [
            dict(table="batched_builder", dataset=f"BA2000/B{B}",
                 algo="rounds", value=stats["rounds"]),
            dict(table="batched_builder", dataset=f"BA2000/B{B}",
                 algo="size_overhead",
                 value=bat.size_entries() / seq.size_entries()),
            dict(table="batched_builder", dataset=f"BA2000/B{B}",
                 algo="size_after_clean",
                 value=cleaned.size_entries() / seq.size_entries()),
            dict(table="batched_builder", dataset=f"BA2000/B{B}",
                 algo="build_time", value=t_bat),
        ]
    rows.append(dict(table="batched_builder", dataset="BA2000/seq",
                     algo="build_time", value=t_seq))
    rows.append(dict(table="batched_builder", dataset="BA2000/seq",
                     algo="rounds", value=g.num_nodes))
    return rows


def bench_label_store(dataset="SO(s)", n_queries=2048):
    """Padded vs CSR-packed label store on a skewed scale-free config:
    store bytes (padded [V, cap] vs flat CSR vs bucket tiles) and µs/query
    of the dense vs segmented device path."""
    rows = []
    g = _social(dataset)
    idx = build_wc_index(g, ordering="degree")
    packed = idx.packed()
    V, cap = idx.num_nodes, idx.label_capacity
    # what the dense pallas engine actually ships: width padded to 128
    from repro.core.wc_index import round_to_lane
    cap128 = round_to_lane(int(idx.count.max()))
    padded_bytes = V * cap128 * 12 + idx.count.nbytes
    rows += [
        dict(table="label_store", dataset=dataset, algo="entries",
             value=idx.size_entries()),
        dict(table="label_store", dataset=dataset, algo="max_label",
             value=int(idx.count.max())),
        dict(table="label_store", dataset=dataset, algo="padded_bytes",
             value=padded_bytes),
        dict(table="label_store", dataset=dataset, algo="csr_bytes",
             value=packed.memory_bytes()),
        dict(table="label_store", dataset=dataset, algo="csr_tile_bytes",
             value=packed.tile_memory_bytes()),
        dict(table="label_store", dataset=dataset, algo="bytes_ratio",
             value=padded_bytes / max(packed.memory_bytes(), 1)),
        dict(table="label_store", dataset=dataset, algo="num_buckets",
             value=packed.num_buckets),
    ]
    s, t, wl = random_queries(g, n_queries, seed=21)
    dense = DeviceQueryEngine(idx)
    seg = DeviceQueryEngine(idx, layout="csr")
    np.asarray(dense.query(s, t, wl))       # warmup compiles
    np.asarray(seg.query(s, t, wl))
    t_dense, _ = _time(lambda: np.asarray(dense.query(s, t, wl)), repeat=3)
    t_seg, _ = _time(lambda: np.asarray(seg.query(s, t, wl)), repeat=3)
    # compare volume: dense pays B * cap128^2, segmented pays the bucket
    # pair widths of each routed sub-batch
    from repro.core.query import plan_query_batch
    widths = packed.bucket_widths.astype(np.int64)
    seg_cmp = sum(len(p.positions) * int(widths[p.bucket_s] * widths[p.bucket_t])
                  for p in plan_query_batch(packed.bucket_of, s, t))
    rows += [
        dict(table="label_store", dataset=dataset, algo="dense_us_per_query",
             value=t_dense / n_queries * 1e6),
        dict(table="label_store", dataset=dataset, algo="seg_us_per_query",
             value=t_seg / n_queries * 1e6),
        dict(table="label_store", dataset=dataset, algo="dense_cmp_volume",
             value=float(n_queries) * cap128 * cap128),
        dict(table="label_store", dataset=dataset, algo="seg_cmp_volume",
             value=float(seg_cmp)),
    ]
    return rows


def bench_serving(batch=4096, n_nodes=3000):
    """Throughput of the serving engine: the single-device batched path vs
    the sharded engine (batch sharded over every attached device, labels
    replicated) — the µs/query comparison CI archives as BENCH_serving.json.
    Run under ``--xla_force_host_platform_device_count=N`` (benchmarks/
    run.py sets it for this suite) to exercise a real multi-device mesh;
    wall-clock on virtual CPU devices measures dispatch overhead, not TPU
    speedup, so the trend under test is correctness of the scaling path.

    Also here: the profile (staircase) workload — every constraint level
    of a pair in ONE label sweep (`query_profile`) vs the L-call
    per-level `query` loop it replaces. The two are asserted bit-identical
    before timing; the acceptance trend is profile_speedup >= 2 at
    L >= 4 levels."""
    import jax

    from repro.core.query import ShardedQueryEngine  # noqa: F401 (doc link)
    from repro.launch.mesh import make_serving_mesh

    rows = []
    name = f"BA{n_nodes}"
    g = scale_free(n_nodes, 4, num_levels=5, seed=13)
    idx = build_wc_index(g, ordering="degree")
    s, t, wl = random_queries(g, batch * 4, seed=5)

    def timed(srv):
        srv.query_many(s[:64], t[:64], wl[:64])  # warm
        t0 = time.perf_counter()
        out = srv.query_many(s, t, wl)
        return time.perf_counter() - t0, out

    dt_single, out_single = timed(WCSDServer(idx, max_batch=batch))
    n_dev = len(jax.devices())
    mesh = make_serving_mesh()
    dt_shard, out_shard = timed(WCSDServer(
        idx, max_batch=batch, backend="sharded", mesh=mesh, layout="padded"))
    assert np.array_equal(out_single, out_shard), \
        "sharded serving diverged from single-device"
    for algo, dt in [("qps", dt_single), ("qps_sharded", dt_shard)]:
        rows.append(dict(table="serving", dataset=name, algo=algo,
                         value=len(s) / dt))
    rows += [
        dict(table="serving", dataset=name, algo="us_per_query",
             value=dt_single / len(s) * 1e6),
        dict(table="serving", dataset=name, algo="us_per_query_sharded",
             value=dt_shard / len(s) * 1e6),
        dict(table="serving", dataset=name, algo="sharded_devices",
             value=n_dev),
        dict(table="serving", dataset=name, algo="sharded_speedup",
             value=dt_single / dt_shard),
    ]
    rows += _bench_continuous_batching(idx, s, t, wl, name,
                                       batch=min(batch, 1024))
    rows += _bench_profile_vs_loop(idx, s[:batch], t[:batch], name)
    rows += _bench_ragged_dispatch()
    rows += _bench_rowsharded_ragged()
    rows += _bench_dma_overlap()
    rows += _bench_dynamic_updates(g, idx, name, batch=min(batch, 1024))
    rows += _bench_resilience(g, idx, name, batch=min(batch, 1024))
    return rows


def _bench_continuous_batching(idx, s, t, wl, name, batch=1024):
    """Continuous-batching serving rows: per-request enqueue->deliver
    latency (p50/p99 µs) of a deadline-flush epoch — submissions trickle
    in one at a time with a `poll` tick between them, so flushes fire at
    min_batch/deadline instead of max_batch (docs/serving.md §1a). The
    p99 ceiling gated by run.py --check is a coarse SLO guard against
    pathological serialization (a flush that re-runs the backlog, a
    request parked forever), not a machine-speed gate — hence its slack."""
    srv = WCSDServer(idx, max_batch=256, max_wait_us=500.0, min_batch=16)
    srv.tracer.start()
    # warm the compile cache by STREAMING (not bulk query_many): deadline
    # flushes compile the small padded shapes the measured epoch will
    # hit, not just the max_batch one
    warm = min(256, batch)
    wrids = []
    for a, b, c in zip(s[:warm], t[:warm], wl[:warm]):
        wrids.append(srv.submit(int(a), int(b), int(c)))
        srv.poll()
    srv.flush()
    for r in wrids:
        srv.result(r)
    srv.tracer.reset()
    lo, hi = warm, warm + batch
    rids = [None] * (hi - lo)
    for i, (a, b, c) in enumerate(zip(s[lo:hi], t[lo:hi], wl[lo:hi])):
        rids[i] = srv.submit(int(a), int(b), int(c))
        srv.poll()
    srv.flush()
    got = np.array([srv.result(r) for r in rids], dtype=np.int32)
    exp = np.asarray(DeviceQueryEngine(idx).query(s[lo:hi], t[lo:hi],
                                                  wl[lo:hi]))
    assert np.array_equal(got, exp), \
        "continuous-batching serving diverged from the device engine"
    lat = srv.latency_summary()
    assert lat["count"] >= len(rids)
    return [
        dict(table="serving", dataset=name, algo="serve_p50_us",
             value=lat["p50_us"]),
        dict(table="serving", dataset=name, algo="serve_p99_us",
             value=lat["p99_us"]),
        dict(table="serving", dataset=name, algo="serve_cb_batches",
             value=srv.stats.batches),
    ]


def _bench_dma_overlap(flush=96, lane=16):
    """The acceptance row of the quad-buffered DMA ring inside the ragged
    megakernel: wall-clock of the SAME worklist through the kernel with
    the production ring depth (``nbuf=4``) vs the single-buffer baseline
    (``nbuf=1``, every tile fetch serialized against the join). The two
    launches are asserted bit-identical first. On TPU the ratio measures
    real fetch/compute overlap; under interpret emulation the copies run
    synchronously either way, so the CI floor only guards the ring
    against ADDING overhead (ratio collapsing well under 1.0)."""
    import jax.numpy as jnp

    import repro.kernels.wcsd_query as wq
    from repro.core.query import emit_ragged_worklist, ragged_worklist_len

    pidx, heavy = make_skewed_store(V=256, W=4, lane=lane, buckets=6)
    ar = pidx.packed(lane=lane).arena(lane=lane)
    rng = np.random.default_rng(11)
    s = rng.integers(0, pidx.num_nodes, flush).astype(np.int32)
    t = rng.integers(0, pidx.num_nodes, flush).astype(np.int32)
    wl = rng.integers(0, pidx.num_levels + 1, flush).astype(np.int32)
    n_salt = min(16, flush // 4)
    s[:n_salt] = np.resize(heavy, n_salt)     # long rows -> deep worklists
    t[n_salt // 2:n_salt + n_salt // 2] = np.resize(heavy, n_salt)
    WLn = ragged_worklist_len(np.asarray(ar.tile_cnt), s, t)
    qidx, stile, ttile = emit_ragged_worklist(
        ar.tile_base, ar.tile_cnt, jnp.asarray(s), jnp.asarray(t),
        worklist_len=WLn)
    wq_lvl = jnp.concatenate([jnp.asarray(wl),
                              jnp.full((1,), 1 << 20, jnp.int32)])

    def run(nbuf):
        return np.asarray(wq.wcsd_query_ragged(
            ar.hub, ar.dist, ar.wlev, ar.tile_lo, ar.tile_hi,
            qidx, stile, ttile, wq_lvl, nbuf=nbuf))

    out4, out1 = run(4), run(1)               # warmup traces, both depths
    assert np.array_equal(out4, out1), \
        "quad-buffered ragged kernel diverged from the nbuf=1 baseline"
    # the gated metric is a RATIO of two wall-clocks: interleave the
    # trials and keep each side's best (same pattern as the dynamic
    # bench), so a load transient hits both sides
    t_multi = t_single = float("inf")
    for _ in range(3):
        t_multi = min(t_multi, _time(run, 4, repeat=2)[0])
        t_single = min(t_single, _time(run, 1, repeat=2)[0])
    name = f"SKEW{pidx.labels.num_buckets}"
    return [
        dict(table="serving", dataset=name, algo="dma_overlap_speedup",
             value=t_single / max(t_multi, 1e-12)),
        dict(table="serving", dataset=name, algo="dma_worklist_entries",
             value=int(qidx.shape[0])),
    ]


def _bench_dynamic_updates(g, idx, name, batch=1024):
    """Dynamic-index serving rows: the cost of folding a graph update into
    the delta label store (``update_apply_us``), of compacting the delta
    back into a fresh packed base (``compact_us``), and the ragged-query
    tax of serving through a NON-EMPTY delta-extended arena relative to
    the static store (``delta_query_overhead``). The overhead ratio is
    the gated acceptance trend (run.py --check ceiling 1.15x): the delta
    only redirects tile pointers inside the one ragged launch per flush,
    so a non-empty delta must not cost a second kernel launch or a
    disproportionately wider worklist."""
    from repro.core.wc_index import DynamicWCIndex

    s, t, wl = random_queries(g, batch, seed=29)

    dyn = DynamicWCIndex(idx, g)
    lv = float(g.levels[len(g.levels) // 2])
    u0, v0 = int(g.edges_src[0]), int(g.edges_dst[0])
    dt_upd, _ = _time(lambda: dyn.apply_updates(
        inserts=[(0, g.num_nodes // 2, lv)], deletes=[(u0, v0)]))
    assert not dyn.delta.is_empty(), \
        "dynamic bench update produced an empty delta; overhead row " \
        "would measure the static path twice"

    static_eng = DeviceQueryEngine(idx, layout="csr", dispatch="ragged")
    dyn_eng = DeviceQueryEngine(dyn, layout="csr", dispatch="ragged")
    np.asarray(static_eng.query(s, t, wl))      # warmup compiles
    np.asarray(dyn_eng.query(s, t, wl))         # retrace: new tile count
    # the gated metric is a RATIO of two wall-clocks: interleave the
    # trials and keep each side's best, so a load transient on a shared
    # CI runner hits both sides instead of skewing the quotient
    t_static = t_delta = float("inf")
    for _ in range(5):
        t_static = min(t_static, _time(
            lambda: np.asarray(static_eng.query(s, t, wl)), repeat=3)[0])
        t_delta = min(t_delta, _time(
            lambda: np.asarray(dyn_eng.query(s, t, wl)), repeat=3)[0])

    dt_cmp, _ = _time(lambda: dyn.compact(ordering="degree",
                                          use_kernel=False))
    return [
        dict(table="serving", dataset=name, algo="update_apply_us",
             value=dt_upd * 1e6),
        dict(table="serving", dataset=name, algo="compact_us",
             value=dt_cmp * 1e6),
        dict(table="serving", dataset=name, algo="delta_query_overhead",
             value=t_delta / max(t_static, 1e-12)),
    ]


def _bench_resilience(g, idx, name, batch=1024):
    """Resilience rows (docs/resilience.md §benchmarks): the wall-clock
    tax of serving one ladder rung DOWN from the primary engine
    (``degraded_mode_overhead`` — csr-ragged primary vs its bucket_pair
    fallback rung, distinct query sets per side so the memo cannot hide
    either engine), and the per-batch cost of the crash-safe update WAL
    (``wal_append_us`` — mean fsync'd append of a small update record).
    Both ceilings gated by run.py --check are coarse SLO guards: the
    overhead ratio catches a fallback rung that silently became
    catastrophically slower than its primary (the ladder would then trade
    an outage for an effective outage), the append ceiling catches a WAL
    that serializes update ingestion."""
    import tempfile

    from repro.checkpoint.ckpt import UpdateWAL
    from repro.core.generators import random_queries

    srv = WCSDServer(idx, layout="csr", dispatch="ragged", max_batch=batch)
    assert srv.mode == "primary"
    qsets = [random_queries(g, batch, seed=61 + i) for i in range(4)]
    for s, t, wl in qsets:                       # warm both rungs' compiles
        srv.query_many(s, t, wl)
    assert srv._demote() and srv.mode == "bucket_pair"
    for s, t, wl in qsets:
        srv.query_many(s, t, wl)
    srv.mode_index = 0
    srv.engine = srv._make_engine()
    # ratio of two wall-clocks: interleave the trials and keep each
    # side's best, same pattern as the other gated ratios; fresh query
    # sets per trial so neither side serves from the memo
    t_prim = t_deg = float("inf")
    for i, (s, t, wl) in enumerate(qsets[:2]):
        sd, td, wld = qsets[2 + i]
        t_prim = min(t_prim, _time(lambda: srv.query_many(s, t, wl))[0])
        assert srv._demote()
        t_deg = min(t_deg, _time(lambda: srv.query_many(sd, td, wld))[0])
        srv.mode_index = 0
        srv.engine = srv._make_engine()
        srv.memo.clear()
        srv.stats.memo_hits = 0
    rows = [dict(table="serving", dataset=name, algo="degraded_mode_overhead",
                 value=t_deg / max(t_prim, 1e-12))]
    with tempfile.TemporaryDirectory() as tmp:
        wal = UpdateWAL(f"{tmp}/bench_wal.log", base_version=0)
        lv = float(g.levels[0])
        n_app = 32
        t0 = time.perf_counter()
        for i in range(n_app):
            wal.append(inserts=[(i, i + 1, lv)], deletes=[(i + 2, i + 3)],
                       graph_version=i + 1)
        dt = time.perf_counter() - t0
        assert len(wal.records()) == n_app
    rows.append(dict(table="serving", dataset=name, algo="wal_append_us",
                     value=dt / n_app * 1e6))
    return rows


def make_skewed_store(V=2048, W=6, lane=32, buckets=8, seed=17, rng=None):
    """A synthetic CSR label store whose row lengths span exactly
    ``buckets`` geometric length buckets (widths lane * 2^b): mostly
    short rows plus one hub-heavy row per wider bucket — the adversarial
    scale-free shape for which the bucket-pair dispatch loop degenerates
    toward buckets^2 kernel launches per flush while the ragged path
    stays at ONE. Synthetic on purpose: the dispatch tax depends only on
    the length distribution, and building a real index with multi-
    thousand-entry rows is not CI material. Rows keep the hub-sorted
    invariant (I1) the arena's tile early-out relies on.

    Shared with tests/test_ragged.py (the adversarial-skew differential
    block drives it with hypothesis-drawn rngs), so the bench and the
    correctness harness cannot drift apart in what "adversarial skew"
    means. Returns (PackedWCIndex, heavy_vertex_ids)."""
    from repro.core.wc_index import PackedLabels, PackedWCIndex

    rng = np.random.default_rng(seed) if rng is None else rng
    lens = rng.integers(1, lane + 1, size=V)
    heavy = rng.choice(V, size=buckets - 1, replace=False)
    for i, v in enumerate(heavy):
        w = lane << (i + 1)                   # one row per wider bucket
        lens[v] = rng.integers(w // 2 + 1, w + 1)
    hub_space = int(lens.max()) * 4
    hub = np.concatenate(
        [np.sort(rng.choice(hub_space, size=k, replace=False))
         for k in lens]).astype(np.int32)
    offsets = np.zeros(V + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    dist = rng.integers(0, 1000, size=len(hub)).astype(np.int32)
    wlev = rng.integers(0, W + 1, size=len(hub)).astype(np.int32)
    store = PackedLabels.from_flat(hub, dist, wlev, offsets, lane=lane)
    assert store.num_buckets == buckets
    ar = np.arange(V, dtype=np.int32)
    pidx = PackedWCIndex(order=ar, rank=ar.copy(),
                         levels=np.arange(W, dtype=np.float64), labels=store)
    return pidx, heavy


def _bench_ragged_dispatch(flush=2048, lane=32):
    """The acceptance row of the single-launch megakernel: ragged vs
    bucket-pair µs/query on a skewed store spanning >= 8 length buckets,
    at the server's default flush size. Both engines run the XLA paths
    and are asserted bit-identical before timing.

    The quantity under test is the DISPATCH tax — one launch + one fused
    H2D + a device-emitted plan, vs one launch per populated bucket pair,
    a host argsort/unique, and per-sub-batch staging — which is exactly
    what the ragged path removes. ``lane=32`` keeps the O(lane^2) label-
    scan compute (bit-identical work on BOTH paths) from hiding that tax
    under CPU XLA wall-clock; on TPU the same comparison runs at the
    production lane of 128 with the launch overhead in play instead."""
    from repro.core.query import DeviceQueryEngine

    pidx, heavy = make_skewed_store(lane=lane)
    rng = np.random.default_rng(5)
    s = rng.integers(0, pidx.num_nodes, flush).astype(np.int32)
    t = rng.integers(0, pidx.num_nodes, flush).astype(np.int32)
    wl = rng.integers(0, pidx.num_levels + 1, flush).astype(np.int32)
    # salt with hub-heavy endpoints (the celebrity-node pattern) on BOTH
    # sides so the flush populates short x short, short x heavy and
    # heavy x heavy pairs — ~20+ bucket-pair launches per flush
    n_salt = min(64, flush // 4)
    s[:n_salt] = np.resize(heavy, n_salt)
    t[n_salt // 2:n_salt + n_salt // 2] = np.resize(heavy, n_salt)
    packed = pidx.labels
    ragged = DeviceQueryEngine(pidx, layout="csr", lane=lane)
    bp = DeviceQueryEngine(pidx, layout="csr", lane=lane,
                           dispatch="bucket_pair")
    out_r = np.asarray(ragged.query(s, t, wl))              # warmup compiles
    out_b = np.asarray(bp.query(s, t, wl))
    assert np.array_equal(out_r, out_b), \
        "ragged dispatch diverged from the bucket-pair oracle"
    t_rag, _ = _time(lambda: np.asarray(ragged.query(s, t, wl)), repeat=5)
    t_bp, _ = _time(lambda: np.asarray(bp.query(s, t, wl)), repeat=5)
    name = f"SKEW{packed.num_buckets}"
    return [
        dict(table="serving", dataset=name, algo="ragged_buckets",
             value=packed.num_buckets),
        dict(table="serving", dataset=name, algo="ragged_us_per_query",
             value=t_rag / len(s) * 1e6),
        dict(table="serving", dataset=name, algo="bucket_pair_us_per_query",
             value=t_bp / len(s) * 1e6),
        dict(table="serving", dataset=name, algo="ragged_speedup",
             value=t_bp / t_rag),
    ]


def _bench_rowsharded_ragged(flush=2048, lane=32):
    """The acceptance row of the ROW-SHARDED ragged path: ragged vs
    bucket-pair µs/query with the label store tile-row-sharded over the
    mesh (``device_budget_bytes=1`` forces mode="sharded_labels"), on the
    same adversarial skewed store as `_bench_ragged_dispatch`. Both
    engines are asserted bit-identical before timing.

    What the ragged path removes here is the PER-BUCKET-PAIR collective
    loop: the bucket-pair engine pays one staged sub-batch plus its row
    gathers for every populated (bucket_s, bucket_t) pair of the flush,
    while the ragged path runs ONE worklist tile gather plus one launch
    per device regardless of the bucket mix. Also rides along:
    ``compressed_bytes_ratio``, the uncompressed/compressed arena bytes
    on this store (the capacity multiplier a fixed HBM budget gains from
    `CompressedArena`)."""
    from repro.core.query import ShardedQueryEngine
    from repro.launch.mesh import make_serving_mesh

    pidx, heavy = make_skewed_store(lane=lane)
    rng = np.random.default_rng(5)
    s = rng.integers(0, pidx.num_nodes, flush).astype(np.int32)
    t = rng.integers(0, pidx.num_nodes, flush).astype(np.int32)
    wl = rng.integers(0, pidx.num_levels + 1, flush).astype(np.int32)
    n_salt = min(64, flush // 4)
    s[:n_salt] = np.resize(heavy, n_salt)
    t[n_salt // 2:n_salt + n_salt // 2] = np.resize(heavy, n_salt)
    mesh = make_serving_mesh()
    ragged = ShardedQueryEngine(pidx, mesh=mesh, layout="csr", lane=lane,
                                device_budget_bytes=1, dispatch="ragged")
    bp = ShardedQueryEngine(pidx, mesh=mesh, layout="csr", lane=lane,
                            device_budget_bytes=1, dispatch="bucket_pair")
    assert ragged.mode == bp.mode == "sharded_labels"
    out_r = np.asarray(ragged.query(s, t, wl))              # warmup compiles
    out_b = np.asarray(bp.query(s, t, wl))
    assert np.array_equal(out_r, out_b), \
        "row-sharded ragged diverged from the bucket-pair oracle"
    t_rag, _ = _time(lambda: np.asarray(ragged.query(s, t, wl)), repeat=5)
    t_bp, _ = _time(lambda: np.asarray(bp.query(s, t, wl)), repeat=5)
    packed = pidx.packed(lane=lane)
    ar_bytes = packed.arena(lane=lane).memory_bytes()
    comp = packed.compressed_arena(lane=lane)
    name = f"SKEW{pidx.labels.num_buckets}"
    return [
        dict(table="serving", dataset=name,
             algo="rowsharded_ragged_us_per_query",
             value=t_rag / len(s) * 1e6),
        dict(table="serving", dataset=name,
             algo="rowsharded_bucket_pair_us_per_query",
             value=t_bp / len(s) * 1e6),
        dict(table="serving", dataset=name, algo="rowsharded_ragged_speedup",
             value=t_bp / t_rag),
        dict(table="serving", dataset=name, algo="compressed_bytes_ratio",
             value=ar_bytes / comp.memory_bytes()),
    ]


def _bench_profile_vs_loop(idx, s, t, name):
    """Profile staircases one-pass vs the per-level query loop, on the CSR
    engine (the layout the one-pass kernel exists for)."""
    eng = DeviceQueryEngine(idx, layout="csr")
    n_levels = idx.num_levels + 1        # staircase covers 0..W inclusive

    def loop_all_levels():
        return np.stack(
            [np.asarray(eng.query(s, t, np.full(len(s), w, np.int32)))
             for w in range(n_levels)], axis=1)

    np.asarray(eng.query_profile(s, t))              # warmup compiles
    loop_all_levels()                                # (full batch shapes)
    t_prof, prof = _time(lambda: np.asarray(eng.query_profile(s, t)),
                         repeat=3)
    t_loop, loop = _time(loop_all_levels, repeat=3)
    assert np.array_equal(prof, loop), \
        "profile diverged from the per-level query loop"
    return [
        dict(table="serving", dataset=name, algo="profile_levels",
             value=n_levels),
        dict(table="serving", dataset=name, algo="profile_us_per_query",
             value=t_prof / len(s) * 1e6),
        dict(table="serving", dataset=name, algo="profile_loop_us_per_query",
             value=t_loop / len(s) * 1e6),
        dict(table="serving", dataset=name, algo="profile_speedup",
             value=t_loop / t_prof),
    ]
