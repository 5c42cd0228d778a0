"""Rank-batched WC-INDEX construction in JAX (beyond-paper optimization).

The paper's Algorithm 3 is strictly sequential across roots (each root's BFS
prunes against labels of every earlier root). That serializes poorly on TPU.
Following the PSL insight (Li et al., SIGMOD'19 [37]) we process roots in
*rank batches*: within a batch, the B constrained BFS runs share one jitted
dense relaxation (segment-max over edges — the same primitive as a GNN
message-passing layer), and pruning queries see the index as of the batch
start.

Consequences (measured in benchmarks/bench_indexing.py):
  + each round is one [B, V] / [B, E] dense step — MXU/VPU friendly, and the
    host loop shrinks by ~B×;
  - intra-batch pruning is deferred, so dominated entries can slip in.
    Soundness/completeness still hold (pruning only ever removes *covered*
    entries, and we only skip prunes, never add spurious paths); minimality
    is restored per (vertex, hub) by a vectorized Pareto post-pass, and the
    residual cross-hub redundancy is reported as `size_overhead`.

Two implementations live here:

  build_wc_index_batched          the original host-orchestrated pipeline:
      every round gathers/prunes in jnp, downloads the [B, V] emission mask
      to host numpy, and appends into padded [V, cap] arrays that serving
      later has to re-pack into the CSR store.
  build_wc_index_batched_packed   the device-resident pipeline: the round
      (prune + emit + relax) runs in Pallas kernels (`kernels/frontier.py`),
      the per-root hub tables T are built on device from the device-side
      partial index, F/R and a per-(root, vertex, level) emission table E
      stay on device for the whole batch (one [B, V, W+1] download per
      batch instead of one [B, V] download per round), and the emissions
      stream into a `PackedLabelsBuilder` whose finalize fuses the Pareto
      post-pass with direct CSR emission — the padded [V, cap] final
      labels are never materialized and serving starts with no repack.

Both report `host_array_syncs` / `host_scalar_syncs` so the benchmark
(`benchmarks/bench_indexing.py`) can show the sync-count collapse.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from .dominance import pareto_filter_grouped
from .graph import Graph, INF_DIST, expand_frontier_csr
from .ordering import make_order
from .wc_index import (PackedLabelsBuilder, PackedWCIndex, WCIndex,
                       _concat_ranges, _ensure_capacity, append_self_entries,
                       round_to_pow2)

DEV_INF = 1 << 29


@functools.partial(jax.jit, static_argnames=("num_segments", "do_prune"))
def _batched_round(F, R, T, hub, dist, wlev, count, root_ranks, edges_src,
                   edges_dst, edges_lvl, rank, d, *, num_segments: int,
                   do_prune: bool):
    """One synchronized BFS round for a batch of roots.

    F: [B, V] frontier quality level (-1 = inactive), R: [B, V] best
    bottleneck level, T: [B, V, W+1] per-root hub tables, labels as padded
    [V, cap] device mirrors. Returns next frontier, R, and the emission mask.
    """
    B, V = F.shape
    active = F >= 0
    Fw = jnp.clip(F, 0, T.shape[-1] - 1)
    if do_prune:
        # query the partial index: min_i dist[v,i] + T[b, hub[v,i], F[b,v]]
        cap = hub.shape[1]
        col = jnp.arange(cap)
        valid = (col[None, :] < count[:, None]) & (hub >= 0)        # [V, cap]
        tv = T[jnp.arange(B)[:, None, None],
               jnp.clip(hub, 0, V - 1)[None, :, :],
               Fw[:, :, None]]                                      # [B,V,cap]
        qual_ok = wlev[None, :, :] >= Fw[:, :, None]
        # clamp before adding: INF + INF must not overflow int32
        ds = jnp.minimum(dist, 1 << 29)
        cand = jnp.where(valid[None] & qual_ok,
                         ds[None] + jnp.minimum(tv, 1 << 29), INF_DIST)
        q = cand.min(axis=2)
        survive = active & (q > d)
    else:
        survive = active
    emit_w = jnp.where(survive, F, -1)

    # relaxation: one fused gather -> min -> segment-max over all B roots
    wp = jnp.minimum(emit_w[:, edges_src], edges_lvl[None, :])      # [B, E2]
    ok_dst = rank[edges_dst][None, :] > root_ranks[:, None]
    wp = jnp.where(ok_dst, wp, -1)
    seg = (edges_dst[None, :] + V * jnp.arange(B)[:, None]).reshape(-1)
    newR = jax.ops.segment_max(wp.reshape(-1), seg,
                               num_segments=num_segments).reshape(B, V)
    newR = jnp.maximum(newR, -1)
    improved = newR > R
    R_next = jnp.where(improved, newR, R)
    F_next = jnp.where(improved, newR, -1)
    return F_next, R_next, emit_w


def _build_T(hub, dist, wlev, count, root_ids, root_ranks, V, W):
    """Host-side per-batch hub tables (numpy; |L(root)| is small)."""
    B = len(root_ids)
    T = np.full((B, V, W + 1), INF_DIST, dtype=np.int32)
    for b, (r, k) in enumerate(zip(root_ids, root_ranks)):
        c = int(count[r])
        if c:
            hr, dr, wr = hub[r, :c], dist[r, :c], wlev[r, :c]
            reps = (wr + 1).astype(np.int64)
            rows = np.repeat(hr.astype(np.int64), reps)
            cols = _concat_ranges(reps)
            np.minimum.at(T[b].reshape(-1), rows * (W + 1) + cols,
                          np.repeat(dr, reps))
        T[b, k, :] = 0
    return T


def build_wc_index_batched(g: Graph, order: Optional[np.ndarray] = None,
                           ordering: str = "degree", batch_size: int = 32,
                           minimalize: bool = True) -> tuple[WCIndex, dict]:
    """Rank-batched construction. Returns (index, stats)."""
    V, W = g.num_nodes, g.num_levels
    if order is None:
        order = make_order(g, ordering)
    order = np.asarray(order, dtype=np.int32)
    rank = np.empty(V, dtype=np.int32)
    rank[order] = np.arange(V, dtype=np.int32)

    B = int(batch_size)
    hub = np.full((V, 4), -1, dtype=np.int32)
    dist = np.full((V, 4), INF_DIST, dtype=np.int32)
    wlev = np.full((V, 4), -1, dtype=np.int32)
    count = np.zeros(V, dtype=np.int32)

    e_src = jnp.asarray(g.edges_src)
    e_dst = jnp.asarray(g.edges_dst)
    e_lvl = jnp.asarray(g.edges_level)
    rank_d = jnp.asarray(rank)
    n_rounds = 0
    raw_entries = 0
    array_syncs = 0
    scalar_syncs = 0

    for start in range(0, V, B):
        roots = order[start:start + B]
        nb = len(roots)
        root_ranks = np.arange(start, start + nb, dtype=np.int32)
        if nb < B:  # pad the tail batch with inert rows
            roots = np.concatenate([roots, np.zeros(B - nb, np.int32)])
            root_ranks = np.concatenate(
                [root_ranks, np.full(B - nb, V + 1, np.int32)])
        T = _build_T(hub, dist, wlev, count, roots[:nb], root_ranks[:nb], V, W)
        # device mirrors, capacity rounded up to limit re-jits
        cap = max(8, 1 << int(np.ceil(np.log2(max(int(count.max()), 1) + 1))))
        hub_d = jnp.asarray(hub[:, :cap] if hub.shape[1] >= cap else
                            np.pad(hub, ((0, 0), (0, cap - hub.shape[1])),
                                   constant_values=-1))
        dist_d = jnp.asarray(dist[:, :cap] if dist.shape[1] >= cap else
                             np.pad(dist, ((0, 0), (0, cap - dist.shape[1])),
                                    constant_values=INF_DIST))
        wlev_d = jnp.asarray(wlev[:, :cap] if wlev.shape[1] >= cap else
                             np.pad(wlev, ((0, 0), (0, cap - wlev.shape[1])),
                                    constant_values=-1))
        count_d = jnp.asarray(count)

        F = np.full((B, V), -1, dtype=np.int32)
        F[np.arange(nb), roots[:nb]] = W
        F = jnp.asarray(F)
        R = F  # at d=0, R == F (root only)
        T_d = jnp.asarray(T)

        d = 0
        emitted: list[tuple[np.ndarray, np.ndarray, np.ndarray, int]] = []
        while True:
            F, R, emit_w = _batched_round(
                F, R, T_d, hub_d, dist_d, wlev_d, count_d,
                jnp.asarray(root_ranks), e_src, e_dst, e_lvl, rank_d,
                jnp.int32(d), num_segments=B * V, do_prune=(d > 0))
            n_rounds += 1
            if d > 0:
                ew = np.asarray(emit_w)        # [B, V] download, every round
                array_syncs += 1
                bs, vs = np.nonzero(ew >= 0)
                if len(bs):
                    emitted.append((bs.astype(np.int32), vs.astype(np.int32),
                                    ew[bs, vs].astype(np.int32), d))
            d += 1
            scalar_syncs += 1
            if not bool(jnp.any(F >= 0)):
                break
        # ---- append batch emissions, grouped by vertex, hub-rank ascending
        if emitted:
            b_all = np.concatenate([e[0] for e in emitted])
            v_all = np.concatenate([e[1] for e in emitted])
            w_all = np.concatenate([e[2] for e in emitted])
            d_all = np.concatenate([np.full(len(e[0]), e[3], np.int32)
                                    for e in emitted])
            raw_entries += len(b_all)
            o = np.lexsort((d_all, b_all, v_all))
            b_all, v_all, w_all, d_all = (b_all[o], v_all[o], w_all[o],
                                          d_all[o])
            hub_new = root_ranks[b_all]
            # per-vertex contiguous runs -> vectorized append
            uniq, run_start = np.unique(v_all, return_index=True)
            run_len = np.diff(np.append(run_start, len(v_all)))
            within = _concat_ranges(run_len)
            pos = count[v_all] + within
            need = int(pos.max()) + 1
            if need > hub.shape[1]:
                new_cap = max(need, hub.shape[1] * 2)
                pad = ((0, 0), (0, new_cap - hub.shape[1]))
                hub = np.pad(hub, pad, constant_values=-1)
                dist = np.pad(dist, pad, constant_values=INF_DIST)
                wlev = np.pad(wlev, pad, constant_values=-1)
            hub[v_all, pos] = hub_new
            dist[v_all, pos] = d_all
            wlev[v_all, pos] = w_all
            count[uniq] += run_len.astype(np.int32)

    stats = {"rounds": n_rounds, "raw_entries": int(raw_entries),
             "batch_size": B, "host_array_syncs": array_syncs,
             "host_scalar_syncs": scalar_syncs}
    if minimalize:
        # vectorized per-(vertex, hub) Pareto sweep to restore minimality
        total = int(count.sum())
        v_flat = np.repeat(np.arange(V, dtype=np.int64), count)
        col = _concat_ranges(count)
        h_flat = hub[v_flat, col]
        d_flat = dist[v_flat, col]
        w_flat = wlev[v_flat, col]
        key = v_flat * V + h_flat  # group by (vertex, hub)
        keep = pareto_filter_grouped(key, d_flat.astype(np.int64),
                                     w_flat.astype(np.int64))
        removed = total - int(keep.sum())
        stats["dominated_removed"] = removed
        if removed:
            v2, h2, d2, w2 = (v_flat[keep], h_flat[keep], d_flat[keep],
                              w_flat[keep])
            count = np.bincount(v2, minlength=V).astype(np.int32)
            capn = max(int(count.max()), 1)
            hub = np.full((V, capn), -1, dtype=np.int32)
            dist = np.full((V, capn), INF_DIST, dtype=np.int32)
            wlev = np.full((V, capn), -1, dtype=np.int32)
            pos = _concat_ranges(count)
            # entries already sorted by (v, hub asc, d asc) after filtering
            o = np.lexsort((d2, h2, v2))
            hub[v2[o], pos] = h2[o]
            dist[v2[o], pos] = d2[o]
            wlev[v2[o], pos] = w2[o]
    hub, dist, wlev, count = append_self_entries(hub, dist, wlev, count,
                                                 rank, W)
    idx = WCIndex(order=order, rank=rank, levels=g.levels.copy(),
                  hub_rank=hub, dist=dist, wlev=wlev, count=count)
    stats["entries"] = idx.size_entries()
    return idx, stats


# --------------------------------------------------- device-resident builder
@functools.partial(jax.jit, static_argnames=("num_nodes", "num_levels"))
def _build_T_device(hub, dist, wlev, roots, root_ranks, *, num_nodes: int,
                    num_levels: int):
    """Per-root hub tables, built on device from the device-side partial
    index: T[b, h, f] = min dist from root b to hub-rank h over paths of
    quality level >= f (INF where unreachable; 0 on the root's own rank).
    Replaces `_build_T`'s host loop + per-batch [B, V, W+1] upload."""
    V, W1 = num_nodes, num_levels + 1
    B = roots.shape[0]
    hr = hub[roots]                                     # [B, cap] hub ranks
    dr = jnp.minimum(dist[roots], DEV_INF)
    wr = wlev[roots]
    feas = jnp.arange(W1)[None, None, :] <= wr[:, :, None]      # [B, cap, W1]
    vals = jnp.where(feas & (hr >= 0)[:, :, None], dr[:, :, None],
                     jnp.int32(INF_DIST))
    T = jnp.full((B, V, W1), INF_DIST, dtype=jnp.int32)
    T = T.at[jnp.arange(B)[:, None], jnp.clip(hr, 0, V - 1), :].min(vals)
    # the root reaches itself at distance 0 at any quality; inert pad rows
    # carry root_ranks == V + 1 and must not touch the table
    self_val = jnp.where((root_ranks < V)[:, None], 0, jnp.int32(INF_DIST))
    T = T.at[jnp.arange(B), jnp.clip(root_ranks, 0, V - 1), :].min(self_val)
    return T


@jax.jit
def _accum_emit(E, emit_w, d):
    """Fold one round's emissions into the on-device emission table:
    E[b, v, w] = the round (== distance) at which (root b, vertex v) emitted
    quality level w. Each cell is written at most once (per (b, v) the
    emitted level strictly increases across rounds), so min() is a plain
    first-write."""
    W1 = E.shape[2]
    onehot = emit_w[:, :, None] == jnp.arange(W1)[None, None, :]
    return jnp.where(onehot, jnp.minimum(E, d), E)


@jax.jit
def _scatter_append(hub, dist, wlev, v, pos, h_new, d_new, w_new):
    """Append new label entries into the device-side padded partial index
    (prune mirror). Out-of-range rows (v == V: length padding) are dropped."""
    return (hub.at[v, pos].set(h_new, mode="drop"),
            dist.at[v, pos].set(d_new, mode="drop"),
            wlev.at[v, pos].set(w_new, mode="drop"))


def build_wc_index_batched_packed(
        g: Graph, order: Optional[np.ndarray] = None,
        ordering: str = "degree", batch_size: int = 32,
        minimalize: bool = True, use_kernel: bool = True,
        interpret: bool | None = None) -> tuple[PackedWCIndex, dict]:
    """Device-resident rank-batched construction emitting CSR directly.

    Same label semantics as `build_wc_index_batched` (identical entry
    multiset before the Pareto pass, identical store after it — asserted by
    tests/test_differential.py), but the pipeline is restructured for the
    accelerator: the per-round prune + emit + relax run as Pallas kernels,
    per-root hub tables are built on device from the device-side partial
    index, and F/R/E state never leaves the device inside a batch. The only
    per-round host sync is the scalar termination check; emissions come
    back once per batch as the [B, V, W+1] table E and stream into a
    `PackedLabelsBuilder`, which finalizes straight into `PackedLabels` —
    no padded [V, cap] final labels, no serve-time repack.

    ``interpret=None`` resolves through `kernels.ops.resolve_interpret`:
    compiled kernels on TPU, interpret emulation elsewhere.

    Returns (PackedWCIndex, stats).
    """
    from ..kernels import ops as kops

    interpret = kops.resolve_interpret(interpret)
    V, W = g.num_nodes, g.num_levels
    if order is None:
        order = make_order(g, ordering)
    order = np.asarray(order, dtype=np.int32)
    rank = np.empty(V, dtype=np.int32)
    rank[order] = np.arange(V, dtype=np.int32)

    B = int(batch_size)
    nbr_np, lvl_np = g.padded_adjacency()
    nbr_d = jnp.asarray(nbr_np)
    lvl_d = jnp.asarray(lvl_np)
    rank_d = jnp.asarray(rank)

    cap = 8
    hub_d = jnp.full((V, cap), -1, dtype=jnp.int32)
    dist_d = jnp.full((V, cap), INF_DIST, dtype=jnp.int32)
    wlev_d = jnp.full((V, cap), -1, dtype=jnp.int32)
    count = np.zeros(V, dtype=np.int64)

    builder = PackedLabelsBuilder(V)
    n_rounds = 0
    raw_entries = 0
    array_syncs = 0
    scalar_syncs = 0

    for start in range(0, V, B):
        roots = order[start:start + B]
        nb = len(roots)
        root_ranks = np.arange(start, start + nb, dtype=np.int32)
        if nb < B:  # pad the tail batch with inert rows
            roots = np.concatenate([roots, np.zeros(B - nb, np.int32)])
            root_ranks = np.concatenate(
                [root_ranks, np.full(B - nb, V + 1, np.int32)])
        rr_d = jnp.asarray(root_ranks)
        T_d = _build_T_device(hub_d, dist_d, wlev_d, jnp.asarray(roots),
                              rr_d, num_nodes=V, num_levels=W)
        F = np.full((B, V), -1, dtype=np.int32)
        F[np.arange(nb), roots[:nb]] = W
        F = jnp.asarray(F)
        R = F
        E = jnp.full((B, V, W + 1), INF_DIST, dtype=jnp.int32)

        d = 0
        while True:
            emit_w = kops.wc_prune_emit(
                F, T_d, hub_d, dist_d, wlev_d, jnp.int32(d),
                do_prune=(d > 0), use_kernel=use_kernel, interpret=interpret)
            if d > 0:
                E = _accum_emit(E, emit_w, jnp.int32(d))
            F, R = kops.wc_relax_batched(
                emit_w, nbr_d, lvl_d, rank_d, rr_d, R,
                use_kernel=use_kernel, interpret=interpret)
            n_rounds += 1
            d += 1
            scalar_syncs += 1
            if not bool(jnp.any(F >= 0)):
                break

        En = np.asarray(E)                  # ONE download per batch
        array_syncs += 1
        bs, vs, ws = np.nonzero(En < int(INF_DIST))
        if len(bs) == 0:
            continue
        ds = En[bs, vs, ws]
        # per (b, v) the emitted level rises with the round, so sorting by
        # (v, b, w) is exactly (vertex, hub rank asc, dist asc)
        o = np.lexsort((ws, bs, vs))
        bs, vs, ws, ds = bs[o], vs[o], ws[o], ds[o]
        hub_new = root_ranks[bs].astype(np.int32)
        raw_entries += len(bs)
        builder.append_batch(vs, hub_new, ds, ws)

        # mirror the new entries into the device-side prune index
        uniq, run_start = np.unique(vs, return_index=True)
        run_len = np.diff(np.append(run_start, len(vs)))
        pos = count[vs] + _concat_ranges(run_len)
        need = int(pos.max()) + 1
        if need > cap:
            new_cap = max(need, cap * 2)
            pad = ((0, 0), (0, new_cap - cap))
            hub_d = jnp.pad(hub_d, pad, constant_values=-1)
            dist_d = jnp.pad(dist_d, pad, constant_values=int(INF_DIST))
            wlev_d = jnp.pad(wlev_d, pad, constant_values=-1)
            cap = new_cap
        # pad the scatter to a power-of-two length (bounded recompiles);
        # padding rows target v == V and are dropped by the scatter
        n = len(vs)
        npad = round_to_pow2(n)
        v_s = np.full(npad, V, dtype=np.int32)
        p_s = np.zeros(npad, dtype=np.int32)
        h_s = np.zeros(npad, dtype=np.int32)
        d_s = np.zeros(npad, dtype=np.int32)
        w_s = np.zeros(npad, dtype=np.int32)
        v_s[:n] = vs
        p_s[:n] = pos
        h_s[:n] = hub_new
        d_s[:n] = ds
        w_s[:n] = ws
        hub_d, dist_d, wlev_d = _scatter_append(
            hub_d, dist_d, wlev_d, jnp.asarray(v_s), jnp.asarray(p_s),
            jnp.asarray(h_s), jnp.asarray(d_s), jnp.asarray(w_s))
        count[uniq] += run_len

    labels, removed = builder.finalize(rank=rank, num_levels=W,
                                       minimalize=minimalize)
    idx = PackedWCIndex(order=order, rank=rank, levels=g.levels.copy(),
                        labels=labels)
    stats = {"rounds": n_rounds, "raw_entries": int(raw_entries),
             "batch_size": B, "host_array_syncs": array_syncs,
             "host_scalar_syncs": scalar_syncs,
             "dominated_removed": removed,
             "entries": labels.size_entries()}
    return idx, stats


def clean_index(idx: WCIndex) -> tuple[WCIndex, int]:
    """PSL-style label cleaning: drop entries that are *unnecessary* (paper's
    minimality definition) — entry (v, hub k, d, w) is removed when the query
    Q(v, order[k], w) is already answered with distance <= d through hubs of
    rank < k. Processing roots in rank order keeps witnesses valid by
    induction on hub rank. Restores sequential-construction minimality for
    the rank-batched builder."""
    V, W = idx.num_nodes, idx.num_levels
    hub, dist, wlev = (idx.hub_rank.copy(), idx.dist.copy(), idx.wlev.copy())
    count = idx.count.copy()
    cap = hub.shape[1]
    col = np.arange(cap)
    removed_total = 0
    # flat view of (entry -> vertex) per hub
    for k in range(V):
        root = int(idx.order[k])
        # vertices holding an entry with hub k (skip self entries)
        vs, cols = np.nonzero((hub == k) & (col[None, :] < count[:, None]))
        sel = vs != root
        vs, cols = vs[sel], cols[sel]
        if len(vs) == 0:
            continue
        d_e = dist[vs, cols]
        w_e = wlev[vs, cols]
        # T for root over hubs < k
        c = int(count[root])
        hr, dr, wr = hub[root, :c], dist[root, :c], wlev[root, :c]
        m = hr < k
        T = np.full((V, W + 1), INF_DIST, dtype=np.int64)
        if m.any():
            reps = (wr[m] + 1).astype(np.int64)
            rows = np.repeat(hr[m].astype(np.int64), reps)
            np.minimum.at(T.reshape(-1), rows * (W + 1) + _concat_ranges(reps),
                          np.repeat(dr[m], reps))
        # query each entry via v's hubs < k
        hv = hub[vs]
        ok = (col[None, :] < count[vs, None]) & (hv >= 0) & (hv < k) & \
             (wlev[vs] >= w_e[:, None])
        tv = T[np.clip(hv, 0, V - 1), w_e[:, None]]
        cand = np.where(ok, dist[vs].astype(np.int64) + tv, INF_DIST)
        drop = cand.min(axis=1) <= d_e
        if drop.any():
            removed_total += int(drop.sum())
            dv, dc = vs[drop], cols[drop]
            o = np.lexsort((-dc, dv))  # right-to-left per vertex: stable cols
            for v, cpos in zip(dv[o], dc[o]):
                cc = int(count[v])
                hub[v, cpos:cc - 1] = hub[v, cpos + 1:cc]
                dist[v, cpos:cc - 1] = dist[v, cpos + 1:cc]
                wlev[v, cpos:cc - 1] = wlev[v, cpos + 1:cc]
                hub[v, cc - 1] = -1
                dist[v, cc - 1] = INF_DIST
                wlev[v, cc - 1] = -1
                count[v] -= 1
    out = WCIndex(order=idx.order, rank=idx.rank, levels=idx.levels,
                  hub_rank=hub, dist=dist, wlev=wlev, count=count)
    return out, removed_total


# --------------------------------------------------------------------------
# Incremental maintenance (docs/dynamic-index.md). The delta layer of
# `core.wc_index.DynamicWCIndex` calls these two functions per update batch:
# `affected_vertices` bounds the blast radius of an edge change, and
# `rebuild_affected_rows` re-runs the pruned rank-ordered rounds for exactly
# those roots, seeded with the current serving rows.


def affected_vertices(g_old: Graph, g_new: Graph, endpoints) -> np.ndarray:
    """Vertices whose label row may change when ``g_old`` becomes ``g_new``.

    The connected-component closure of the touched ``endpoints`` at level 0
    (all edges), over the UNION of the two graphs. Conservative but provably
    sufficient: a root in a different component (in both graphs) explores an
    unchanged subgraph, seeds its hub table from labels whose hubs live in
    that unchanged component, and prunes against rows of vertices it can
    reach there — every input to its BFS is unchanged, so its emissions are
    too. Conversely every emission of an affected root targets a vertex of
    the closure, so label corrections never escape the returned set.
    """
    V = g_new.num_nodes
    seen = np.zeros(V, dtype=bool)
    f = np.unique(np.asarray(list(endpoints), dtype=np.int64))
    f = f[(f >= 0) & (f < V)]
    seen[f] = True
    f = f.astype(np.int32)
    while len(f):
        nxt = [expand_frontier_csr(g, f)[1] for g in (g_old, g_new)]
        nxt = np.unique(np.concatenate(nxt).astype(np.int64))
        nxt = nxt[~seen[nxt]]
        seen[nxt] = True
        f = nxt.astype(np.int32)
    return np.flatnonzero(seen).astype(np.int32)


def rebuild_affected_rows(g: Graph, order: np.ndarray, rank: np.ndarray,
                          num_levels: int, merged_flat, affected) -> dict:
    """Recompute the label rows of ``affected`` vertices on the mutated graph.

    Re-runs the sequential Algorithm-3 loop of `wc_index.build_wc_index` for
    the affected ROOTS only (ascending rank), seeded with the current
    serving rows (``merged_flat``: flat hub/dist/wlev + offsets) minus every
    entry whose hub is an affected root and minus the trailing self entries
    (the loop emulates the root's self entry via ``T[k, :] = 0``, exactly
    like the from-scratch build). Soundness of the seeded pruning: at root
    ``k``, unaffected seed entries with hub < k are exactly what a
    from-scratch run over ``g`` would have emitted by then (closure
    argument in `affected_vertices`), affected hubs < k were re-run earlier
    in this very loop, and entries with hub >= k are masked out of the hub
    table so pruning never consults a lower-priority witness.

    Returns ``{vertex: (hub, dist, wlev)}`` — full replacement rows
    (hub-sorted, staircase-minimal per hub group, self-entry-terminated)
    for every vertex whose row may have changed.
    """
    V, W = g.num_nodes, int(num_levels)
    order = np.asarray(order, dtype=np.int32)
    rank = np.asarray(rank, dtype=np.int32)
    fhub, fdist, fwlev, offs = merged_flat
    affected = np.asarray(affected, dtype=np.int64)
    aff_ranks = np.sort(rank[affected].astype(np.int64))
    is_aff_rank = np.zeros(V, dtype=bool)
    is_aff_rank[aff_ranks] = True

    # ---- seed padded working rows from the current serving store ----------
    lens = (offs[1:] - offs[:-1]).astype(np.int64)
    rows_of = np.repeat(np.arange(V, dtype=np.int64), lens)
    keep = ~is_aff_rank[np.clip(fhub, 0, V - 1)]
    keep[offs[1:] - 1] = False  # every row terminates with its self entry
    krows = rows_of[keep]
    count = np.bincount(krows, minlength=V).astype(np.int32)
    cap = max(int(count.max()) if V else 1, 8)
    hub = np.full((V, cap), -1, dtype=np.int32)
    dist = np.full((V, cap), INF_DIST, dtype=np.int32)
    wlev = np.full((V, cap), -1, dtype=np.int32)
    cols = _concat_ranges(count.astype(np.int64))
    hub[krows, cols] = fhub[keep]
    dist[krows, cols] = fdist[keep]
    wlev[krows, cols] = fwlev[keep]
    # rows that lost an entry are stale even if the re-run emits nothing back
    dropped = ~keep
    dropped[offs[1:] - 1] = False  # self entries are re-appended, not drops
    touched = np.zeros(V, dtype=bool)
    touched[affected] = True
    touched[rows_of[dropped]] = True

    # ---- re-run the pruned rank-ordered rounds for affected roots ---------
    T = np.full((V, W + 1), INF_DIST, dtype=np.int32)
    touched_T: list[np.ndarray] = []
    R = np.full(V, -1, dtype=np.int32)
    touched_R: list[np.ndarray] = []
    for k in aff_ranks:
        k = int(k)
        root = int(order[k])
        c = int(count[root])
        if c:
            hr, dr, wr = hub[root, :c], dist[root, :c], wlev[root, :c]
            pre = hr < k  # only hubs the from-scratch run would know by now
            hr, dr, wr = hr[pre], dr[pre], wr[pre]
            if len(hr):
                reps = (wr + 1).astype(np.int64)
                rows = np.repeat(hr.astype(np.int64), reps)
                np.minimum.at(T.reshape(-1),
                              rows * (W + 1) + _concat_ranges(reps),
                              np.repeat(dr, reps))
                touched_T.append(hr.copy())
        T[k, :] = 0
        touched_T.append(np.array([k], dtype=np.int32))
        R[root] = W
        touched_R.append(np.array([root], dtype=np.int32))

        frontier_v = np.array([root], dtype=np.int32)
        frontier_w = np.array([W], dtype=np.int32)
        d = 0
        while len(frontier_v):
            if d > 0:
                capn = hub.shape[1]
                col = np.arange(capn)
                m = (col[None, :] < count[frontier_v, None]) & \
                    (wlev[frontier_v] >= frontier_w[:, None])
                hubs = hub[frontier_v]
                # hubs >= k stay INF in T: never prune on a lower-priority
                # witness (they may not exist in the from-scratch run yet)
                tv = T[np.clip(hubs, 0, V - 1), frontier_w[:, None]]
                cand = np.where(
                    m, dist[frontier_v].astype(np.int64) + tv, INF_DIST)
                survive = cand.min(axis=1) > d
                frontier_v = frontier_v[survive]
                frontier_w = frontier_w[survive]
                if len(frontier_v) == 0:
                    break
                hub, dist, wlev = _ensure_capacity((hub, dist, wlev), count,
                                                   frontier_v)
                pos = count[frontier_v]
                hub[frontier_v, pos] = k
                dist[frontier_v, pos] = d
                wlev[frontier_v, pos] = frontier_w
                count[frontier_v] += 1
                touched[frontier_v] = True
            src_pos, nbrs, lvls = expand_frontier_csr(g, frontier_v)
            w_new = np.minimum(frontier_w[src_pos], lvls)
            valid = (rank[nbrs] > k) & (w_new > R[nbrs])
            nbrs, w_new = nbrs[valid], w_new[valid]
            if len(nbrs):
                np.maximum.at(R, nbrs, w_new)
                cands = np.unique(nbrs)
                touched_R.append(cands)
                frontier_v = cands
                frontier_w = R[cands].copy()
            else:
                frontier_v = np.zeros(0, dtype=np.int32)
                frontier_w = np.zeros(0, dtype=np.int32)
            d += 1
        for arr in touched_T:
            T[arr] = INF_DIST
        touched_T.clear()
        for arr in touched_R:
            R[arr] = -1
        touched_R.clear()

    # ---- assemble full replacement rows (hub-sorted + self entry) ---------
    out = {}
    for v in np.flatnonzero(touched):
        v = int(v)
        c = int(count[v])
        h, dd, w = hub[v, :c], dist[v, :c], wlev[v, :c]
        o = np.lexsort((dd, h))
        h, dd, w = h[o], dd[o], w[o]
        out[v] = (np.append(h, rank[v]).astype(np.int32),
                  np.append(dd, 0).astype(np.int32),
                  np.append(w, W).astype(np.int32))
    return out
