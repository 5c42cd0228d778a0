"""Smoke run of the WC-Index serving path on a TPU.

One chip (the default): build the road-routing index
``road_grid(100, 100, num_levels=5, seed=0)`` with the host builder, serve
it through ``WCSDServer(idx, mesh=make_serving_mesh(),
**serve_config().server_kwargs())`` — CSR arena, compiled ragged Pallas
kernels, continuous batching — and check that

- every answer equals the host sort-merge of Algorithm 5
  (`WCIndex.query_one`), a sample equals the BFS reference
  (`core.ref.wcsd_bfs`), and every profile equals the per-level answers;
- the chip did the work: the server stayed on the "primary" rung with
  zero retries and demotions, every answer was computed there, the
  engine runs compiled (not interpreted) Pallas kernels, and the compiled
  ragged program holds a Mosaic ``tpu_custom_call``.

``--chips 4`` runs only the multi-chip path on the same index and
queries: `ShardedQueryEngine` replicated and row-sharded, plain and
compressed, and `WCSDServer` over the four-chip mesh, each compared bit
for bit with `DeviceQueryEngine` on one device.

Run from the root of the repository::

    python chip_smoke.py [--chips 4]

The last line of standard output is the JSON verdict
``{"ok": true, "device": {...}}``; any failed check exits non-zero
before it is printed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
GRID = (100, 100)
NUM_LEVELS = 5
N_STREAM = 3000       # scalar queries streamed with the deadline on
N_PROFILE = 300       # profile (staircase) queries
N_BFS = 300           # answers also checked against the BFS reference


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def make_queries(V: int, W: int, n_scalar: int, n_profile: int, seed: int):
    """Distinct undirected (s, t, w) keys and distinct (s, t) profile
    pairs, so no request is answered from the server's memo: every answer
    comes out of a device flush."""
    rng = np.random.default_rng(seed)

    def distinct(n, levels):
        s = rng.integers(0, V, 4 * n)
        t = rng.integers(0, V, 4 * n)
        w = rng.integers(0, levels, 4 * n)
        lo, hi = np.minimum(s, t), np.maximum(s, t)
        _, first = np.unique((lo * V + hi) * levels + w, return_index=True)
        keep = np.sort(first)[:n]
        check(len(keep) == n, "could not draw enough distinct queries")
        return s[keep].astype(np.int32), t[keep].astype(np.int32), \
            w[keep].astype(np.int32)

    return distinct(n_scalar, W + 1), distinct(n_profile, 1)[:2]


def build(seed: int):
    from repro.core.generators import road_grid
    from repro.core.wc_index import build_wc_index
    g = road_grid(*GRID, num_levels=NUM_LEVELS, seed=seed)
    t0 = time.perf_counter()
    idx = build_wc_index(g, ordering="degree")
    return g, idx, time.perf_counter() - t0


def host_answers(idx, s, t, w):
    return np.array([idx.query_one(int(a), int(b), int(c))
                     for a, b, c in zip(s, t, w)], dtype=np.int64)


def check_profiles(idx, ps, pt, profiles, tag: str) -> None:
    W = idx.num_levels
    for w in range(W + 1):
        exp = host_answers(idx, ps, pt, np.full(len(ps), w))
        bad = np.flatnonzero(profiles[:, w] != exp)
        check(not len(bad), f"{tag}: profile level {w} differs from the "
                            f"host merge at {len(bad)} pairs")


def serve_stream(srv, s, t, w, ps, pt, full_batch):
    """Drive `srv` through submit/poll with its continuous-batching
    deadline on, then force one flush of exactly ``full_batch`` queries
    (deadline off until the cap fires), then the profiles. Returns
    (answers, modes, profiles, profile modes, first-flush seconds)."""
    n = len(s)
    stream = n - full_batch
    rids = []
    t0 = time.perf_counter()
    first_flush_s = None
    for i in range(stream):
        rids.append(srv.submit(int(s[i]), int(t[i]), int(w[i])))
        if first_flush_s is None and srv.stats.batches:
            first_flush_s = time.perf_counter() - t0
        if i % 16 == 15:
            srv.poll()
    srv.flush()
    deadline = srv.max_wait_us
    srv.max_wait_us = None            # the max_batch cap alone fires next
    batches = srv.stats.batches
    for i in range(stream, n):
        rids.append(srv.submit(int(s[i]), int(t[i]), int(w[i])))
    check(srv.stats.batches == batches + 1,
          f"{full_batch} queued queries did not fire one full flush")
    srv.max_wait_us = deadline
    srv.flush()
    prids = []
    for i in range(len(ps)):
        prids.append(srv.submit_profile(int(ps[i]), int(pt[i])))
        if i % 16 == 15:
            srv.poll()
    srv.flush()
    got = [srv.result_with_mode(r) for r in rids]
    prof = [srv.profile_result_with_mode(r) for r in prids]
    return (np.array([v for v, _ in got], dtype=np.int64),
            {m for _, m in got},
            np.stack([p for p, _ in prof]).astype(np.int64),
            {m for _, m in prof}, first_flush_s)


def check_primary(srv, modes, tag: str) -> None:
    st = srv.stats
    check(srv.mode == "primary", f"{tag}: server left the primary rung "
                                 f"(mode {srv.mode!r})")
    for name in ("demotions", "error_retries", "timeout_retries",
                 "exhausted"):
        check(getattr(st, name) == 0,
              f"{tag}: stats.{name} = {getattr(st, name)}")
    check(modes == {"primary"}, f"{tag}: answers came from modes {modes}")
    eng = srv.engine
    check(eng.interpret is False and eng.use_pallas is True,
          f"{tag}: engine interpret={eng.interpret} "
          f"use_pallas={eng.use_pallas}")


def ragged_program_text(eng, s, t, w):
    """Compile the ragged plan + launch jit the engine serves with, on the
    engine's own arena, and return (HLO text, compile seconds)."""
    from repro.core.query import ragged_query_batch, ragged_worklist_len
    stq = np.stack([s, t, w]).astype(np.int32)
    wl_len = ragged_worklist_len(eng.packed.arena().tile_cnt, s, t)
    t0 = time.perf_counter()
    compiled = ragged_query_batch.lower(
        *eng._arena, stq, worklist_len=wl_len, interpret=eng.interpret,
        use_kernel=eng.use_pallas, compressed=eng.compressed).compile()
    return compiled.as_text(), time.perf_counter() - t0


def one_chip(args, device) -> None:
    import jax

    from repro.configs.wcsd_serve import serve_config
    from repro.core.ref import wcsd_bfs
    from repro.core.serve import WCSDServer
    from repro.launch.mesh import make_serving_mesh

    g, idx, build_s = build(args.seed)
    cfg = serve_config()
    (s, t, w), (ps, pt) = make_queries(g.num_nodes, g.num_levels,
                                       N_STREAM + cfg.max_batch, N_PROFILE,
                                       args.seed + 1)
    srv = WCSDServer(idx, mesh=make_serving_mesh(), **cfg.server_kwargs())
    arena_bytes = srv.engine.store_bytes_per_device
    limit = device.memory_stats()["bytes_limit"]
    log(f"index: road_grid{GRID} V={g.num_nodes} W={g.num_levels} "
        f"entries={idx.size_entries()} arena_bytes={arena_bytes} "
        f"({100.0 * arena_bytes / limit:.3f}% of bytes_limit={limit}) "
        f"host_build_s={build_s:.1f}")

    got, modes, prof, pmodes, first_flush_s = serve_stream(
        srv, s, t, w, ps, pt, cfg.max_batch)
    check_primary(srv, modes | pmodes, "server")
    check(srv.stats.max_batch == cfg.max_batch,
          f"largest flush {srv.stats.max_batch} != {cfg.max_batch}")
    exp = host_answers(idx, s, t, w)
    bad = np.flatnonzero(got != exp)
    check(not len(bad), f"{len(bad)} answers differ from the host merge, "
                        f"first at {bad[:5].tolist()}")
    pick = np.random.default_rng(args.seed + 2).choice(len(s), N_BFS,
                                                       replace=False)
    for i in pick:
        ref = wcsd_bfs(g, int(s[i]), int(t[i]), int(w[i]))
        check(got[i] == ref, f"query {i} ({s[i]}, {t[i]}, {w[i]}): "
                             f"served {got[i]}, BFS {ref}")
    check_profiles(idx, ps, pt, prof, "server")
    text, compile_s = ragged_program_text(srv.engine, s[:cfg.max_batch],
                                          t[:cfg.max_batch],
                                          w[:cfg.max_batch])
    check("tpu_custom_call" in text,
          "the compiled ragged program holds no tpu_custom_call")
    st = srv.stats
    log(f"served: {len(got)} answers + {len(prof)} profiles in "
        f"{st.batches} flushes (largest {st.max_batch}, "
        f"{st.deadline_flushes} deadline, {st.opportunistic_flushes} "
        f"opportunistic); first flush incl. compile {first_flush_s:.2f} s; "
        f"ragged jit lower+compile {compile_s:.2f} s "
        f"({jax.config.jax_compilation_cache_dir or 'no cache dir'})")
    log(f"checked: {len(got)} vs host merge, {N_BFS} vs BFS, "
        f"{len(prof)} profiles x {g.num_levels + 1} levels; "
        "mode primary, 0 retries, 0 demotions, tpu_custom_call present")


def four_chips(args) -> None:
    import jax

    from repro.configs.wcsd_serve import serve_config
    from repro.core.query import DeviceQueryEngine, ShardedQueryEngine
    from repro.core.serve import WCSDServer
    from repro.launch.mesh import make_serving_mesh

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, "
                             f"JAX sees {len(devices)}")
    g, idx, build_s = build(args.seed)
    cfg = serve_config()
    (s, t, w), (ps, pt) = make_queries(g.num_nodes, g.num_levels,
                                       N_STREAM + cfg.max_batch, N_PROFILE,
                                       args.seed + 1)
    log(f"index: road_grid{GRID} V={g.num_nodes} "
        f"entries={idx.size_entries()} host_build_s={build_s:.1f}")
    mesh = make_serving_mesh()
    # a full max_batch flush and the profiles, engine by engine
    qs, qt, qw = s[:cfg.max_batch], t[:cfg.max_batch], w[:cfg.max_batch]
    ref = {}
    for compressed in (False, True):
        one = DeviceQueryEngine(idx, layout="csr", use_pallas=True,
                                compressed=compressed)
        check(one.compressed == compressed, "store overflows the "
                                            "compressed format")
        check(one._arena[0].sharding.device_set == {devices[0]},
              "single-device engine is not on one device")
        ref[compressed] = (np.asarray(one.query(qs, qt, qw)),
                           one.query_profile(ps, pt))
        for budget in (None, 1):
            eng = ShardedQueryEngine(idx, mesh=mesh, layout="csr",
                                     use_pallas=True, compressed=compressed,
                                     device_budget_bytes=budget)
            tag = f"{eng.mode}{' compressed' if compressed else ''}"
            check(eng.compressed == compressed and eng.interpret is False,
                  f"{tag}: compressed={eng.compressed} "
                  f"interpret={eng.interpret}")
            placed = (eng._arena[0], eng._put_staged(
                np.zeros((3, 2 * len(devices)), np.int32)))
            for a in placed:
                check(len(a.sharding.device_set) == 4,
                      f"{tag}: an array sits on "
                      f"{len(a.sharding.device_set)} devices")
            got = np.asarray(eng.query(qs, qt, qw))
            check(np.array_equal(got, ref[compressed][0]),
                  f"{tag}: {int((got != ref[compressed][0]).sum())} "
                  "answers differ from one device")
            check(np.array_equal(eng.query_profile(ps, pt),
                                 ref[compressed][1]),
                  f"{tag}: profiles differ from one device")
            log(f"OK {tag}: {len(qs)} answers + {len(ps)} profiles "
                "bit-identical to DeviceQueryEngine on one device")
    srv = WCSDServer(idx, mesh=mesh, **cfg.server_kwargs())
    got, modes, prof, pmodes, _ = serve_stream(srv, s, t, w, ps, pt,
                                               cfg.max_batch)
    check_primary(srv, modes | pmodes, "4-chip server")
    one = DeviceQueryEngine(idx, layout="csr", use_pallas=True)
    check(np.array_equal(got, np.asarray(one.query(s, t, w))),
          "4-chip server answers differ from one device")
    check(np.array_equal(prof, ref[False][1]),
          "4-chip server profiles differ from one device")
    log(f"OK 4-chip WCSDServer(serve_config()): {len(got)} answers + "
        f"{len(prof)} profiles in {srv.stats.batches} flushes, "
        "bit-identical to one device, mode primary, 0 retries")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}; run from the "
              "repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 3
    device = jax.devices()[0]
    log(f"device: {device.device_kind} x{len(jax.devices())}, "
        f"jax {jax.__version__}, compile cache {cache}")
    try:
        if args.chips == 4:
            four_chips(args)
        else:
            one_chip(args, device)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
