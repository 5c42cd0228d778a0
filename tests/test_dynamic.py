"""Dynamic-index differential harness: serving must stay EXACT through
arbitrary interleavings of edge inserts, deletes, queries and compactions.

The headline schedule-replay harness generates 200 randomized instances
(deterministic under the `_hypo_shim` fallback), each a small random graph
plus a random update/compact schedule, and after EVERY mutation checks the
full (s, t, w_level) grid three ways:

  dynamic engine over the delta-extended store   (the system under test)
  a from-scratch `build_wc_index_batched_packed` rebuild on the mutated
  graph, queried via the host sort-merge          (the rebuild oracle)
  the per-level BFS sweep                         (structurally independent)

Coverage: 6 in-process blocks x 25 examples run the single-device engine
modes (padded, csr ragged, csr ragged compressed, csr bucket_pair, and the
dynamic `WCSDServer` surface incl. staleness flags), and one 8-virtual-
device subprocess runs 2 blocks x 25 through `ShardedQueryEngine` in
replicated AND row-sharded (`device_budget_bytes=1`) modes, compressed
alternating — 6 * 25 + 50 = 200 instances.

Also here: the compaction-equivalence property test (`compact()` output
byte-identical to a from-scratch packed build on the mutated graph — the
PR 2 pack-after-build lock extended to dynamic stores), persistence
round-trip + fault-injection tests (truncated file, corrupted magic,
version mismatch, mid-write crash), `mutate_edges` unit tests, and the
`built_indices` version-keyed-cache regression test.
"""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest
from _hypo_shim import given, settings, st  # hypothesis or fallback

from repro.checkpoint.ckpt import (IndexHeaderError, IndexPersistenceError,
                                   IndexTruncatedError, IndexVersionError,
                                   WCX_MAGIC, load_packed_index,
                                   save_packed_index)
from repro.checkpoint.fault import MidWriteCrash, crashing_open
from repro.core.baselines import constrained_distance_grid
from repro.core.generators import erdos_renyi
from repro.core.graph import Graph, mutate_edges
from repro.core.query import DeviceQueryEngine
from repro.core.resilience import UnknownRequestError
from repro.core.serve import WCSDServer
from repro.core.wc_index import DynamicWCIndex, build_wc_index
from repro.core.wc_index_batched import (affected_vertices,
                                         build_wc_index_batched_packed,
                                         rebuild_affected_rows)

# one build config shared by the base build, `compact()` and the rebuild
# oracle, so compaction equivalence is a pure byte comparison
BUILD_KW = dict(ordering="degree", batch_size=16, use_kernel=False)

N_BLOCKS = 6
EXAMPLES_PER_BLOCK = 25
N_SHARDED = 50          # subprocess instances; total = 6 * 25 + 50 = 200
_instances_run = [0]


def _full_grid(V, W):
    s, t, w = np.meshgrid(np.arange(V), np.arange(V), np.arange(W + 1),
                          indexing="ij")
    return (s.ravel().astype(np.int32), t.ravel().astype(np.int32),
            w.ravel().astype(np.int32))


def _random_mutation(rng, g):
    """One randomized update batch: 1-2 inserts/deletes over ``g``."""
    inserts, deletes = [], []
    for _ in range(int(rng.integers(1, 3))):
        half = np.flatnonzero(g.edges_src < g.edges_dst)
        if rng.random() < 0.45 and len(half):
            e = int(rng.choice(half))
            deletes.append((int(g.edges_src[e]), int(g.edges_dst[e])))
        else:
            u, v = (int(x) for x in rng.choice(g.num_nodes, 2, replace=False))
            inserts.append((u, v, float(rng.choice(g.levels))))
    return inserts, deletes


def _check_exact(answer_fn, g, tag):
    """Full-grid equality vs the BFS sweep AND the from-scratch rebuild."""
    V, W = g.num_nodes, g.num_levels
    s, t, wl = _full_grid(V, W)
    exp = constrained_distance_grid(g)[s, t, wl]
    got = np.asarray(answer_fn(s, t, wl))
    np.testing.assert_array_equal(got, exp, err_msg=tag)
    oracle, _ = build_wc_index_batched_packed(g, **BUILD_KW)
    reb = np.array([oracle.query_one(int(a), int(b), int(c))
                    for a, b, c in zip(s, t, wl)], dtype=np.int32)
    np.testing.assert_array_equal(got, reb, err_msg=tag + " vs rebuild")


# mode per block: layout/dispatch/compressed/kernel and whether the
# schedule drives a DeviceQueryEngine directly or the WCSDServer surface
_MODES = [
    dict(layout="padded", dispatch="ragged", compressed=False,
         use_pallas=False, server=False),
    dict(layout="csr", dispatch="ragged", compressed=False,
         use_pallas=True, server=False),
    dict(layout="csr", dispatch="ragged", compressed=True,
         use_pallas=True, server=False),
    dict(layout="csr", dispatch="bucket_pair", compressed=False,
         use_pallas=True, server=False),
    dict(layout="csr", dispatch="ragged", compressed=False,
         use_pallas=False, server=True),
    dict(layout="padded", dispatch="ragged", compressed=False,
         use_pallas=False, server=True),
]


@pytest.mark.parametrize("block", range(N_BLOCKS))
@given(st.sampled_from([8, 10, 12]), st.sampled_from([2.5, 3.5, 4.5]),
       st.sampled_from([2, 3]), st.integers(0, 100_000))
@settings(max_examples=EXAMPLES_PER_BLOCK, deadline=None, derandomize=True)
def test_schedule_replay_differential(block, n, deg, levels, seed):
    mode = _MODES[block]
    rng = np.random.default_rng(seed + 15485863 * block)
    g = erdos_renyi(n, deg, num_levels=levels, seed=seed + 7919 * block)
    idx, _ = build_wc_index_batched_packed(g, **BUILD_KW)

    if mode["server"]:
        srv = WCSDServer(idx, graph=g, layout=mode["layout"],
                         dispatch=mode["dispatch"],
                         compressed=mode["compressed"],
                         use_pallas=mode["use_pallas"], interpret=True,
                         max_batch=2048, compact_threshold=None,
                         compact_kwargs=BUILD_KW)
        target = srv
        answer = srv.query_many
    else:
        target = DynamicWCIndex(idx, g)

        lane_kw = {"lane": 16} if mode["layout"] == "csr" else {}

        def answer(s, t, wl):
            eng = DeviceQueryEngine(target, layout=mode["layout"],
                                    dispatch=mode["dispatch"],
                                    compressed=mode["compressed"],
                                    use_pallas=mode["use_pallas"],
                                    interpret=True, **lane_kw)
            return eng.query(s, t, wl)

    n_ops = int(rng.integers(2, 4))
    for op in range(n_ops):
        gcur = target.graph if not mode["server"] else target.index.graph
        inserts, deletes = _random_mutation(rng, gcur)
        target.apply_updates(inserts=inserts, deletes=deletes)
        gcur = target.graph if not mode["server"] else target.index.graph
        _check_exact(answer, gcur, f"block={block} op={op} after update")
        if rng.random() < 0.3:
            target.compact(**({} if mode["server"] else BUILD_KW))
            dyn = target if not mode["server"] else target.index
            assert dyn.delta.is_empty()
            _check_exact(answer, gcur, f"block={block} op={op} after compact")
    _instances_run[0] += 1


def test_differential_coverage_target():
    """Acceptance: harness configured for >= 200 generated instances
    (6 x 25 in-process + 50 sharded in the subprocess leg below)."""
    assert N_BLOCKS * EXAMPLES_PER_BLOCK + N_SHARDED >= 200
    if _instances_run[0]:
        assert _instances_run[0] % EXAMPLES_PER_BLOCK == 0


# ------------------------------------------- sharded modes (8 devices)
_SHARDED_DYNAMIC_PROG = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.launch.compile_cache import enable_compile_cache
enable_compile_cache()
import numpy as np
from repro.core.baselines import constrained_distance_grid
from repro.core.generators import erdos_renyi
from repro.core.query import ShardedQueryEngine
from repro.core.wc_index import DynamicWCIndex
from repro.core.wc_index_batched import build_wc_index_batched_packed
from repro.launch.mesh import make_serving_mesh

assert len(jax.devices()) == 8
mesh = make_serving_mesh()
BUILD_KW = dict(ordering="degree", batch_size=16, use_kernel=False)
N = 50
ran = 0
rng = np.random.default_rng(20260808)
for i in range(N):
    n = [8, 10, 12][int(rng.integers(3))]
    deg = [2.5, 3.5, 4.5][int(rng.integers(3))]
    levels = [2, 3][int(rng.integers(2))]
    g = erdos_renyi(n, deg, num_levels=levels,
                    seed=int(rng.integers(0, 100_001)))
    idx, _ = build_wc_index_batched_packed(g, **BUILD_KW)
    dyn = DynamicWCIndex(idx, g)
    # replicated on even instances, row-sharded labels on odd; compressed
    # alternating independently
    budget = None if i % 2 == 0 else 1
    compressed = i % 4 < 2
    for op in range(2):
        gcur = dyn.graph
        inserts, deletes = [], []
        half = np.flatnonzero(gcur.edges_src < gcur.edges_dst)
        if rng.random() < 0.45 and len(half):
            e = int(rng.choice(half))
            deletes.append((int(gcur.edges_src[e]), int(gcur.edges_dst[e])))
        else:
            u, v = (int(x) for x in
                    rng.choice(gcur.num_nodes, 2, replace=False))
            inserts.append((u, v, float(rng.choice(gcur.levels))))
        dyn.apply_updates(inserts=inserts, deletes=deletes)
        if op == 1 and i % 5 == 0:
            dyn.compact(**BUILD_KW)
            assert dyn.delta.is_empty()
        g2 = dyn.graph
        V, W = g2.num_nodes, g2.num_levels
        s, t, w = np.meshgrid(np.arange(V), np.arange(V), np.arange(W + 1),
                              indexing="ij")
        s, t, w = (a.ravel().astype(np.int32) for a in (s, t, w))
        D = constrained_distance_grid(g2)
        exp = D[s, t, w]
        eng = ShardedQueryEngine(
            dyn, mesh=mesh, layout="csr", dispatch="ragged",
            device_budget_bytes=budget, use_pallas=(ran % 7 == 0),
            interpret=True, compressed=compressed)
        assert eng.mode == ("replicated" if budget is None
                            else "sharded_labels")
        np.testing.assert_array_equal(np.asarray(eng.query(s, t, w)), exp)
        ps, pt = s[::W + 1], t[::W + 1]
        np.testing.assert_array_equal(
            np.asarray(eng.query_profile(ps, pt)), D[ps, pt, :])
        # rebuild-oracle identity, not just BFS agreement
        oracle, _ = build_wc_index_batched_packed(g2, **BUILD_KW)
        reb = np.array([oracle.query_one(int(a), int(b), int(c))
                        for a, b, c in zip(s, t, w)], dtype=np.int32)
        np.testing.assert_array_equal(np.asarray(eng.query(s, t, w)), reb)
    ran += 1
assert ran == N == 50
print(f"OK sharded dynamic {ran} instances")
'''


def test_sharded_dynamic_differential_on_8_devices():
    """Replicated AND row-sharded `ShardedQueryEngine` over the delta-
    extended store, compressed alternating, on 8 virtual devices: 50
    schedule-replay instances, every answer bit-identical to the BFS sweep
    and the from-scratch rebuild (query + profile)."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = {**os.environ, "PYTHONPATH": src, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SHARDED_DYNAMIC_PROG],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "OK sharded dynamic 50 instances" in r.stdout


# --------------------------------------------------- compaction equivalence
@given(st.integers(0, 100_000))
@settings(max_examples=10, deadline=None, derandomize=True)
def test_compact_byte_identical_to_fresh_build(seed):
    """For any update schedule, `compact()` leaves the dynamic index's base
    store byte-identical to `build_wc_index_batched_packed` on the mutated
    graph — every CSR array AND the bucket routing tables (extends the
    PR 2 pack-after-build lock to dynamic stores)."""
    rng = np.random.default_rng(seed)
    g = erdos_renyi(int(rng.integers(10, 30)), 3.0, num_levels=3,
                    seed=seed + 13)
    idx, _ = build_wc_index_batched_packed(g, **BUILD_KW)
    dyn = DynamicWCIndex(idx, g)
    for _ in range(int(rng.integers(1, 4))):
        inserts, deletes = _random_mutation(rng, dyn.graph)
        dyn.apply_updates(inserts=inserts, deletes=deletes)
    dyn.compact(**BUILD_KW)
    ref, _ = build_wc_index_batched_packed(dyn.graph, **BUILD_KW)
    np.testing.assert_array_equal(dyn.base.order, ref.order)
    np.testing.assert_array_equal(dyn.base.rank, ref.rank)
    for field in ("hub_rank", "dist", "wlev", "offsets", "bucket_widths",
                  "bucket_of", "slot_of"):
        np.testing.assert_array_equal(getattr(dyn.base.labels, field),
                                      getattr(ref.labels, field), field)
    assert dyn.delta.is_empty() and dyn.delta_ratio() == 0.0


def test_delta_store_accounting():
    """Delta bookkeeping: corrections/tombstones count the symmetric
    difference vs the base store, rows identical to base drop out, and
    `delta_ratio` drives the server's auto-compaction trigger."""
    # sequential-built base: the incremental recompute IS the sequential
    # loop, so undoing an update drains every corrected row back to its
    # base row (the batched-built base keeps deferred-prune extras the
    # sequential recompute drops, so its delta only shrinks, not empties)
    g = erdos_renyi(30, 3.0, num_levels=3, seed=4)
    idx = build_wc_index(g, ordering="degree")
    dyn = DynamicWCIndex(idx, g)
    assert dyn.delta.is_empty() and dyn.delta_ratio() == 0.0
    u, v = int(g.edges_src[0]), int(g.edges_dst[0])
    dyn.apply_updates(deletes=[(u, v)])
    assert not dyn.delta.is_empty()
    assert dyn.delta.delta_entries() > 0
    lvl = float(g.levels[int(g.edges_level[0])])
    dyn.apply_updates(inserts=[(u, v, lvl)])
    assert dyn.delta.is_empty()
    assert dyn.graph_version == 2  # version still advances monotonically

    # auto-compaction: a tiny threshold triggers on the first update
    g2 = erdos_renyi(20, 3.0, num_levels=3, seed=5)
    idx2, _ = build_wc_index_batched_packed(g2, **BUILD_KW)
    srv = WCSDServer(idx2, graph=g2, layout="csr", interpret=True,
                     compact_threshold=1e-9, compact_kwargs=BUILD_KW)
    stats = srv.apply_updates(
        deletes=[(int(g2.edges_src[0]), int(g2.edges_dst[0]))])
    assert stats["compacted"] is True
    assert srv.index.delta.is_empty()


# ------------------------------------------------------- server semantics
def test_server_staleness_flags():
    """Answers computed against an older graph version read back stale;
    post-update answers do not. The staleness stamp survives the memo."""
    g = erdos_renyi(24, 3.0, num_levels=3, seed=11)
    idx, _ = build_wc_index_batched_packed(g, **BUILD_KW)
    srv = WCSDServer(idx, graph=g, layout="csr", interpret=True,
                     max_batch=512, compact_threshold=None,
                     compact_kwargs=BUILD_KW)
    r_old = srv.submit(0, 5, 1)
    p_old = srv.submit_profile(1, 6)
    assert srv.graph_version == 0
    srv.apply_updates(inserts=[(0, 5, float(g.levels[0]))])
    assert srv.graph_version == 1
    _, stale = srv.result_with_staleness(r_old)
    assert stale is True
    prof, pstale = srv.profile_result_with_staleness(p_old)
    assert pstale is True and prof is not None
    r_new = srv.submit(0, 5, 0)
    val, stale = srv.result_with_staleness(r_new)
    D = constrained_distance_grid(srv.index.graph)
    assert val == int(D[0, 5, 0]) and stale is False
    # memo hit after an update serves the post-update answer, not stale
    r_memo = srv.submit(0, 5, 0)
    val2, stale2 = srv.result_with_staleness(r_memo)
    assert val2 == val and stale2 is False
    assert srv.stats.memo_hits >= 1
    # unknown rid is the typed read-once contract
    with pytest.raises(UnknownRequestError):
        srv.result_with_staleness(10_000)


def test_server_requires_graph_for_updates():
    g = erdos_renyi(10, 3.0, num_levels=2, seed=0)
    idx, _ = build_wc_index_batched_packed(g, **BUILD_KW)
    srv = WCSDServer(idx, layout="csr", interpret=True)
    with pytest.raises(ValueError, match="dynamic server"):
        srv.apply_updates(inserts=[(0, 1, float(g.levels[0]))])
    with pytest.raises(ValueError, match="dynamic server"):
        srv.compact()
    eng = DeviceQueryEngine(idx, layout="csr", interpret=True)
    with pytest.raises(ValueError, match="injected engine"):
        WCSDServer(engine=eng, graph=g)


# ----------------------------------------------------------- mutate_edges
def test_mutate_edges_semantics():
    g = erdos_renyi(12, 3.0, num_levels=3, seed=7)
    u, v = int(g.edges_src[0]), int(g.edges_dst[0])
    # upsert replaces the quality of an existing edge (from_edges alone
    # would keep the max-quality duplicate)
    q_new = float(g.levels[0])
    g2 = mutate_edges(g, inserts=[(u, v, q_new)])
    m = ((g2.edges_src == u) & (g2.edges_dst == v))
    assert g2.levels[g2.edges_level[m]][0] == q_new
    assert g2.version == g.version + 1
    np.testing.assert_array_equal(g2.levels, g.levels)  # table preserved
    # deletes are orientation-insensitive
    g3 = mutate_edges(g2, deletes=[(v, u)])
    assert not ((g3.edges_src == u) & (g3.edges_dst == v)).any()
    # the level table survives even when a delete removes the last edge of
    # a quality level
    assert len(g3.levels) == len(g.levels)
    with pytest.raises(ValueError, match="not in the graph's level table"):
        mutate_edges(g, inserts=[(0, 1, 123.456)])
    with pytest.raises(ValueError, match="self loop"):
        mutate_edges(g, inserts=[(3, 3, float(g.levels[0]))])


def test_affected_vertices_is_component_closure():
    # two disjoint components: 0-1-2 and 3-4; touching 0 must never mark
    # the other component as affected
    u = np.array([0, 1, 3], dtype=np.int32)
    v = np.array([1, 2, 4], dtype=np.int32)
    q = np.array([1.0, 1.0, 1.0])
    g = Graph.from_edges(5, u, v, q)
    g2 = mutate_edges(g, deletes=[(0, 1)])
    aff = affected_vertices(g, g2, [0, 1])
    assert set(aff.tolist()) == {0, 1, 2}
    # an insert bridging the components affects both closures
    g3 = mutate_edges(g, inserts=[(2, 3, 1.0)])
    aff2 = affected_vertices(g, g3, [2, 3])
    assert set(aff2.tolist()) == {0, 1, 2, 3, 4}


# ------------------------------------------------------------- persistence
def _build_small(seed=3):
    g = erdos_renyi(30, 3.0, num_levels=4, seed=seed)
    idx, _ = build_wc_index_batched_packed(g, **BUILD_KW)
    return g, idx


def test_save_load_round_trip_bit_identical(tmp_path):
    """save() -> load() round-trips every array bit-identically, the mmap
    load is zero-copy (arrays stay backed by the file mapping), and an
    engine over the loaded index serves bit-identically to the builder's."""
    g, idx = _build_small()
    p = str(tmp_path / "idx.wcx")
    save_packed_index(p, idx, graph_version=g.version)
    loaded, header = load_packed_index(p)
    assert header["graph_version"] == g.version
    assert header["num_nodes"] == g.num_nodes
    np.testing.assert_array_equal(loaded.order, idx.order)
    np.testing.assert_array_equal(loaded.rank, idx.rank)
    np.testing.assert_array_equal(loaded.levels, idx.levels)
    for field in ("hub_rank", "dist", "wlev", "offsets", "bucket_widths",
                  "bucket_of", "slot_of"):
        np.testing.assert_array_equal(getattr(loaded.labels, field),
                                      getattr(idx.labels, field), field)

    def mmap_backed(a):
        while a is not None and not isinstance(a, np.memmap):
            a = getattr(a, "base", None)
        return isinstance(a, np.memmap)

    assert all(mmap_backed(getattr(loaded.labels, f))
               for f in ("hub_rank", "dist", "wlev", "offsets"))

    s, t, wl = _full_grid(g.num_nodes, g.num_levels)
    for eng_idx in (idx, loaded):
        eng = DeviceQueryEngine(eng_idx, layout="csr", dispatch="ragged",
                                interpret=True)
        np.testing.assert_array_equal(
            np.asarray(eng.query(s, t, wl)),
            constrained_distance_grid(g)[s, t, wl])
    # eager (non-mmap) load agrees bit-for-bit too
    eager, _ = load_packed_index(p, mmap=False)
    np.testing.assert_array_equal(eager.labels.hub_rank,
                                  loaded.labels.hub_rank)


def test_load_rejects_corrupted_magic(tmp_path):
    g, idx = _build_small()
    p = str(tmp_path / "idx.wcx")
    save_packed_index(p, idx)
    with open(p, "r+b") as f:
        f.write(b"NOTANIDX")
    with pytest.raises(IndexHeaderError, match="magic"):
        load_packed_index(p)
    # typed errors share the IndexPersistenceError base
    assert issubclass(IndexHeaderError, IndexPersistenceError)
    assert issubclass(IndexTruncatedError, IndexPersistenceError)
    assert issubclass(IndexVersionError, IndexPersistenceError)


def test_load_rejects_truncated_file(tmp_path):
    g, idx = _build_small()
    p = str(tmp_path / "idx.wcx")
    save_packed_index(p, idx)
    data = open(p, "rb").read()
    # every truncation point must refuse cleanly — header, table, payload
    for frac in (0.01, 0.3, 0.99):
        cut = str(tmp_path / f"cut{frac}.wcx")
        with open(cut, "wb") as f:
            f.write(data[:int(len(data) * frac)])
        with pytest.raises(IndexTruncatedError):
            load_packed_index(cut)


def test_load_rejects_version_mismatch(tmp_path):
    g, idx = _build_small()
    p = str(tmp_path / "idx.wcx")
    save_packed_index(p, idx)
    data = open(p, "rb").read()
    hlen = int.from_bytes(data[len(WCX_MAGIC):len(WCX_MAGIC) + 8], "little")
    hdr = data[len(WCX_MAGIC) + 8:len(WCX_MAGIC) + 8 + hlen]
    # same-length patch keeps every offset in the file valid
    patched = hdr.replace(b'"version": 2', b'"version":99')
    assert patched != hdr and len(patched) == len(hdr)
    vf = str(tmp_path / "ver.wcx")
    with open(vf, "wb") as f:
        f.write(data[:len(WCX_MAGIC) + 8] + patched
                + data[len(WCX_MAGIC) + 8 + hlen:])
    with pytest.raises(IndexVersionError, match="format version"):
        load_packed_index(vf)


def test_mid_write_crash_never_tears_the_served_file(tmp_path):
    """A crash mid-write (injected via checkpoint/fault.crashing_open)
    leaves the target path untouched — the previous complete index keeps
    serving — and the torn tmp file itself refuses to load."""
    g, idx = _build_small()
    p = str(tmp_path / "idx.wcx")
    save_packed_index(p, idx, graph_version=1)
    before = open(p, "rb").read()
    for budget in (4, 100, len(before) // 2, len(before) - 16):
        with pytest.raises(MidWriteCrash):
            save_packed_index(p, idx, graph_version=2,
                              _open=crashing_open(budget))
        assert open(p, "rb").read() == before  # target never replaced
        tmp = p + ".tmp"
        if os.path.exists(tmp):
            with pytest.raises((IndexTruncatedError, IndexHeaderError)):
                load_packed_index(tmp)
            os.remove(tmp)
    _, header = load_packed_index(p)
    assert header["graph_version"] == 1  # still the pre-crash version


def test_load_rejects_bit_flips_in_every_blob(tmp_path):
    """Fault matrix, corruption leg (docs/resilience.md §integrity): ONE
    flipped byte in ANY payload blob must surface as a typed
    IndexIntegrityError at load — never a silent load that would serve a
    wrong distance. Probes one byte per blob (first, middle, last)."""
    from repro.checkpoint.ckpt import _WCX_ALIGN, _wcx_arrays
    from repro.checkpoint.fault import flip_byte_on_disk
    from repro.core.resilience import IndexIntegrityError

    g, idx = _build_small(seed=5)
    p = str(tmp_path / "idx.wcx")
    save_packed_index(p, idx)
    data = open(p, "rb").read()
    hlen = int.from_bytes(data[len(WCX_MAGIC):len(WCX_MAGIC) + 8], "little")
    header = json.loads(data[len(WCX_MAGIC) + 8:len(WCX_MAGIC) + 8 + hlen])
    assert set(header["arrays"]) == set(_wcx_arrays(idx))
    raw = len(WCX_MAGIC) + 8 + hlen
    payload0 = -(-raw // _WCX_ALIGN) * _WCX_ALIGN  # save()'s aligned base
    for name, spec in header["arrays"].items():
        nbytes = int(spec["nbytes"])
        if nbytes == 0:
            continue
        for rel in (0, nbytes // 2, nbytes - 1):
            off = payload0 + spec["offset"] + rel
            orig = flip_byte_on_disk(p, off, mask=0x40)
            with pytest.raises(IndexIntegrityError, match=name):
                load_packed_index(p, mmap=False)
            # verify=False documents the override exists; then restore
            load_packed_index(p, mmap=False, verify=False)
            assert flip_byte_on_disk(p, off, mask=0x40) == orig ^ 0x40
    loaded, _ = load_packed_index(p, mmap=False)   # healed file loads clean
    np.testing.assert_array_equal(loaded.labels.hub_rank,
                                  idx.labels.hub_rank)


def test_verify_integrity_on_demand(tmp_path):
    """`verify_integrity()` on a live index/arena: passes on clean state,
    names the corrupted blob after an in-memory bit-flip, and passes
    again once the flip is undone."""
    from repro.checkpoint.fault import flip_array_cell
    from repro.core.resilience import IndexIntegrityError

    g, idx = _build_small(seed=7)
    idx.verify_integrity()                  # stamps the baseline
    idx.verify_integrity()                  # clean re-check passes
    undo = flip_array_cell(idx.labels.dist, flat_index=1, mask=4)
    with pytest.raises(IndexIntegrityError, match="dist"):
        idx.verify_integrity()
    undo()
    idx.verify_integrity()
    # the lane-tiled arena carries its own checksums
    ar = idx.labels.arena(lane=16)
    ar.verify_integrity()
    undo = flip_array_cell(ar.hub, flat_index=0, mask=1)
    with pytest.raises(IndexIntegrityError, match="hub"):
        ar.verify_integrity()
    undo()
    ar.verify_integrity()
    # a loaded index carries the on-disk checksums as its baseline
    p = str(tmp_path / "idx.wcx")
    save_packed_index(p, idx)
    loaded, _ = load_packed_index(p, mmap=False)
    loaded.verify_integrity()


def test_warm_start_then_serve_dynamic(tmp_path):
    """The warm-start scenario end to end: persist, mmap-load in a fresh
    index object, wrap dynamic, apply updates, stay exact."""
    g, idx = _build_small(seed=9)
    p = str(tmp_path / "idx.wcx")
    save_packed_index(p, idx, graph_version=g.version)
    loaded, _ = load_packed_index(p)
    dyn = DynamicWCIndex(loaded, g)
    dyn.apply_updates(inserts=[(0, 9, float(g.levels[1]))])
    g2 = dyn.graph
    s, t, wl = _full_grid(g2.num_nodes, g2.num_levels)
    eng = DeviceQueryEngine(dyn, layout="csr", dispatch="ragged",
                            interpret=True)
    np.testing.assert_array_equal(
        np.asarray(eng.query(s, t, wl)),
        constrained_distance_grid(g2)[s, t, wl])


# -------------------------------------------------- conftest cache keying
def test_built_indices_cache_keys_on_graph_version(built_indices):
    """Regression (dynamic tests must not poison static fixtures): if the
    cached graph object's version moves — i.e. a dynamic test mutated the
    fixture in place — the next `built_indices` call rebuilds instead of
    returning the stale (graph, index) pair."""
    kwargs = dict(num_nodes=14, avg_degree=3.0, num_levels=2, seed=12345)
    g1, idx1 = built_indices("erdos_renyi", **kwargs)
    g1b, idx1b = built_indices("erdos_renyi", **kwargs)
    assert g1 is g1b and idx1 is idx1b  # cache hit while version unchanged
    # simulate a dynamic test bumping the cached graph's version in place
    object.__setattr__(g1, "version", g1.version + 1)
    g2, idx2 = built_indices("erdos_renyi", **kwargs)
    assert g2 is not g1 and idx2 is not idx1
    assert g2.version == 0  # fresh build over a fresh graph
    g3, idx3 = built_indices("erdos_renyi", **kwargs)
    assert g3 is g2 and idx3 is idx2  # fresh pair is cached again
