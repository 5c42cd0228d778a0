"""Device-side batched WCSD query engine.

The serving hot path: given device-resident labels, answer batches of
(s, t, w_level) queries. Implementations:

  - `query_batch_jnp`: pure-jnp masked outer join (oracle; also what the XLA
    fallback runs when Pallas is unavailable).
  - `kernels.ops.wcsd_query`: the Pallas TPU kernel (VMEM-tiled).
  - `WCIndex.query_one`: host sort-merge (paper Alg. 5), for tiny workloads.
  - the CSR layout's ragged megakernel path (default): ONE launch per flush
    over the lane-tiled `LabelArena`, batch plan = a device-emitted
    tile-pair worklist (`emit_ragged_worklist`) — the bucket-pair dispatch
    loop survives as `dispatch="bucket_pair"`, the differential oracle.
    See docs/query-engine.md for the dispatch-cost model.

Distribution (`ShardedQueryEngine`): queries are embarrassingly parallel ->
shard the batch axis over ("data",) / ("pod", "data") and replicate the
label store on every device; when the store exceeds a per-device HBM
budget, fall back to sharding the *vertex* (tile-row) axis of the label
arrays over the same devices and gather the two label rows per query with
the `row_gather_psum` collective — per query only the touched rows cross
the interconnect.

Profiles (`query_profile` on both engines): the full ``dist(s, t, w)``
staircase for every level from ONE sweep of the two label rows —
`_staircase_from_rows` is the shared min-scan core, docs/profile-queries.md
the spec. Same planner, same placements, L× fewer row gathers than the
per-level loop it replaces.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.wcsd_query import pad_group_rows
from . import tracing
from .graph import INF_DIST
from .wc_index import (PackedLabels, PackedWCIndex, WCIndex, ceil_to,
                       round_to_lane, round_to_pow2)

DEV_INF = jnp.int32(1 << 29)


@functools.partial(jax.jit, static_argnames=())
def query_batch_jnp(hub, dist, wlev, count, s, t, w_level):
    """[B] w-constrained distances via masked outer join over padded labels.

    hub/dist/wlev: [V, L] int32 padded label arrays, count: [V].
    s/t/w_level: [B] int32 queries. Returns int32 [B] (INF_DIST = no path).
    """
    L = hub.shape[1]
    col = jnp.arange(L)
    hs, ht = hub[s], hub[t]                       # [B, L]
    ms = (col[None, :] < count[s, None]) & (wlev[s] >= w_level[:, None])
    mt = (col[None, :] < count[t, None]) & (wlev[t] >= w_level[:, None])
    ds = jnp.where(ms, jnp.minimum(dist[s], DEV_INF), DEV_INF)
    dt = jnp.where(mt, jnp.minimum(dist[t], DEV_INF), DEV_INF)
    eq = hs[:, :, None] == ht[:, None, :]         # [B, L, L]
    dsum = ds[:, :, None] + dt[:, None, :]
    best = jnp.where(eq, dsum, DEV_INF).min(axis=(1, 2))
    return jnp.where(best >= DEV_INF, INF_DIST, best).astype(jnp.int32)


def _staircase_from_rows(hs, ds, ws, ht, dt, wt, num_levels: int):
    """[B, *] masked label rows -> [B, W + 1] profile staircase.

    The shared min-scan core of every profile path: a hub meet (i, j) is
    feasible at exactly the levels <= min(ws[i], wt[j]), so its distance
    sum lands in one pair-level bucket and the suffix min over buckets is
    the full staircase ``dist(s, t, w)`` for w = 0..W. ds/dt must already
    be clamped to DEV_INF (pads included); ws/wt pads must be -1 so they
    fall below every bucket. Widths of the two sides may differ."""
    eq = hs[:, :, None] == ht[:, None, :]
    dsum = jnp.where(eq, ds[:, :, None] + dt[:, None, :], DEV_INF)
    mw = jnp.minimum(ws[:, :, None], wt[:, None, :])
    bucket = jnp.stack([jnp.where(mw == lev, dsum, DEV_INF).min(axis=(1, 2))
                        for lev in range(num_levels + 1)], axis=1)
    prof = jax.lax.cummin(bucket, axis=1, reverse=True)
    return jnp.where(prof >= DEV_INF, INF_DIST, prof).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_levels",))
def profile_batch_jnp(hub, dist, wlev, count, s, t, *, num_levels: int):
    """[B, W + 1] staircases via ONE masked outer join over padded labels.

    The profile analogue of `query_batch_jnp`: both label rows are
    gathered once and every constraint level 0..W is answered from that
    single sweep — ``out[:, w] == query_batch_jnp(..., w)`` pointwise."""
    L = hub.shape[1]
    col = jnp.arange(L)

    def side(v):
        m = col[None, :] < count[v, None]
        d = jnp.where(m, jnp.minimum(dist[v], DEV_INF), DEV_INF)
        w = jnp.where(m, wlev[v], -1)
        return hub[v], d, w

    return _staircase_from_rows(*side(s), *side(t), num_levels)


@jax.jit
def query_batch_sorted_jnp(hub, dist, wlev, count, s, t, w_level):
    """Theorem-3-aware variant: per hub only the FIRST quality-feasible entry
    matters, so we first reduce each side to its per-hub minimum distance
    (segmented min over the sorted-by-hub label row), then do the outer join
    on the reduced rows. Same result, ~W× fewer outer-compare FLOPs when
    labels hold multiple quality tiers per hub."""
    L = hub.shape[1]
    col = jnp.arange(L)

    def reduce_side(v):
        h = hub[v]
        m = (col[None, :] < count[v, None]) & (wlev[v] >= w_level[:, None])
        d = jnp.where(m, jnp.minimum(dist[v], DEV_INF), DEV_INF)
        # entries are hub-sorted; keep min dist at first occurrence of hub
        first = jnp.concatenate([jnp.ones_like(h[:, :1], dtype=bool),
                                 h[:, 1:] != h[:, :-1]], axis=1)
        # backward running-min within equal-hub runs via reverse scan trick:
        # since within a hub run dist ascends (Thm. 3), the first feasible
        # entry already has the run's min -> segment min == min over run
        run_min = jax.lax.associative_scan(
            lambda a, b: (jnp.where(b[1], b[0], jnp.minimum(a[0], b[0])),
                          a[1] | b[1]),
            (d, first), axis=1)[0]
        # value at last element of each run = run min; scatter back: for the
        # outer join it is enough to keep per-entry run_min at run heads and
        # DEV_INF elsewhere (dedup), so equal hubs do not double-count.
        last = jnp.concatenate([h[:, :-1] != h[:, 1:],
                                jnp.ones_like(h[:, :1], dtype=bool)], axis=1)
        red = jnp.where(last, run_min, DEV_INF)
        return h, red

    hs, ds = reduce_side(s)
    ht, dt = reduce_side(t)
    eq = hs[:, :, None] == ht[:, None, :]
    best = jnp.where(eq, ds[:, :, None] + dt[:, None, :], DEV_INF)
    best = best.min(axis=(1, 2))
    return jnp.where(best >= DEV_INF, INF_DIST, best).astype(jnp.int32)


@dataclasses.dataclass
class QuerySubBatch:
    """One bucket-pair slice of an incoming batch (see `plan_query_batch`)."""
    bucket_s: int
    bucket_t: int
    positions: np.ndarray  # [n] indices into the original batch


def plan_query_batch(bucket_of: np.ndarray, s: np.ndarray, t: np.ndarray,
                     num_buckets: int | None = None) -> list[QuerySubBatch]:
    """Group a (s, t) batch by the (bucket(s), bucket(t)) pair.

    The dense path pays ``B * cap^2`` hub compares where cap is the *global*
    max label length; routing each query to the tile pair sized for its own
    endpoints bounds the compare volume per query by
    ``width(bucket(s)) * width(bucket(t))`` — on skewed label distributions
    almost every query lands in the smallest bucket pair. Sub-batches come
    back in a deterministic (bucket_s, bucket_t) order and their position
    arrays partition ``arange(len(s))``.

    ``num_buckets``: pass the store's bucket count (the engines cache it)
    to skip the O(V) ``bucket_of.max()`` scan this planner otherwise pays
    on EVERY flush.
    """
    bucket_of = np.asarray(bucket_of)
    bs = bucket_of[np.asarray(s)]
    bt = bucket_of[np.asarray(t)]
    if num_buckets is not None:
        nb = int(num_buckets)
    else:
        nb = int(bucket_of.max()) + 1 if len(bucket_of) else 1
    key = bs.astype(np.int64) * nb + bt
    order = np.argsort(key, kind="stable")
    uniq, starts = np.unique(key[order], return_index=True)
    bounds = np.append(starts, len(order))
    return [QuerySubBatch(bucket_s=int(k // nb), bucket_t=int(k % nb),
                          positions=order[a:b])
            for k, a, b in zip(uniq, bounds[:-1], bounds[1:])]


# -------------------------------------------------------- ragged dispatch
@functools.partial(jax.jit, static_argnames=("worklist_len",))
def emit_ragged_worklist(tile_base, tile_cnt, s, t, *, worklist_len: int):
    """Device-side ragged plan: the flat (query, s_tile, t_tile) worklist.

    Query q over rows with ``tile_cnt[s[q]]`` x ``tile_cnt[t[q]]`` arena
    tiles owns that many consecutive work items (query-major via an
    exclusive prefix sum — no wasted lanes on skewed length mixes, and the
    megakernel's output row is revisited only consecutively). This IS the
    batch plan, jitted: it replaces the per-flush host argsort/unique of
    the bucket-pair planner, so the host contributes only the O(B)
    worklist-capacity sum (`ragged_worklist_len`).

    Returns (qidx, stile, ttile), all int32 [worklist_len]. Work items
    beyond the real total carry ``qidx == len(s)`` — the caller's kernel
    output owns one trash row at that index — and tile 0 on both sides.
    """
    Q = s.shape[0]
    ts = tile_cnt[s].astype(jnp.int32)
    tt = tile_cnt[t].astype(jnp.int32)
    c = ts * tt                                            # [Q] >= 1
    cum = jnp.cumsum(c)
    k = jnp.arange(worklist_len, dtype=jnp.int32)
    qidx = jnp.searchsorted(cum, k, side="right").astype(jnp.int32)
    qc = jnp.minimum(qidx, Q - 1)                          # clamp for pads
    local = k - (cum[qc] - c[qc])
    pad = qidx >= Q
    stile = jnp.where(pad, 0, tile_base[s[qc]] + local // tt[qc])
    ttile = jnp.where(pad, 0, tile_base[t[qc]] + local % tt[qc])
    return qidx, stile, ttile


def ragged_worklist_len(tile_cnt: np.ndarray, s: np.ndarray, t: np.ndarray
                        ) -> int:
    """Host-side worklist capacity: the total tile-pair count of the batch,
    rounded to the next power of two (compiled-shape count stays
    logarithmic). O(B) gather + sum — the ONLY per-flush host arithmetic
    left on the ragged path."""
    total = int(tile_cnt[s].astype(np.int64) @ tile_cnt[t].astype(np.int64))
    return round_to_pow2(total)


@functools.partial(jax.jit, static_argnames=("worklist_len", "interpret",
                                             "use_kernel", "compressed"))
def ragged_query_batch(hub, dist, wlev, tile_lo, tile_hi,
                       tile_base, tile_cnt, stq, *, worklist_len: int,
                       interpret: bool = True, use_kernel: bool = True,
                       compressed: bool = False):
    """Plan + launch, fused into ONE device call: emit the worklist from
    the staged queries and answer every query with a single ragged kernel
    launch (several equal ones when the worklist outgrows one launch's
    scalar memory — `kernels.wcsd_query.ragged_launches`).

    hub..tile_cnt: the `LabelArena` arrays; stq: [3, Q] staged
    (s, t, w_level) — one H2D transfer carries the whole batch. Returns
    [Q] int32 distances (INF_DIST when no feasible path); pad queries
    should carry an infeasible level and are the caller's to discard.
    ``compressed=True`` reads `CompressedArena` arrays instead (hub deltas,
    float distances, int8 levels — decoded in-kernel); hub/dist/wlev must
    then be the compressed trio, the index arrays are shared."""
    from ..kernels import ops as kops
    s, t, wl = stq[0], stq[1], stq[2]
    with jax.named_scope("wcsd.emit_worklist"):
        qidx, stile, ttile = emit_ragged_worklist(
            tile_base, tile_cnt, s, t, worklist_len=worklist_len)
    with jax.named_scope("wcsd.join"):
        # one trash output row for worklist pads; no stored wlev reaches
        # 2^20, so its level is infeasible at every entry
        wq = jnp.concatenate([wl, jnp.full((1,), 1 << 20, jnp.int32)])
        op = (kops.wcsd_query_ragged_compressed if compressed
              else kops.wcsd_query_ragged)
        out = op(hub, dist, wlev, tile_lo, tile_hi, qidx, stile, ttile,
                 wq, interpret=interpret, use_kernel=use_kernel)
    with jax.named_scope("wcsd.unstage"):
        return out[: s.shape[0]]


@functools.partial(jax.jit, static_argnames=("worklist_len", "num_levels",
                                             "interpret", "use_kernel",
                                             "compressed"))
def ragged_profile_batch(hub, dist, wlev, tile_lo, tile_hi,
                         tile_base, tile_cnt, stq, *, worklist_len: int,
                         num_levels: int, interpret: bool = True,
                         use_kernel: bool = True, compressed: bool = False):
    """Profile twin of `ragged_query_batch`: stq is [2, Q] staged (s, t);
    every constraint level of every query is answered by the one launch.
    Returns [Q, num_levels + 1] staircases."""
    from ..kernels import ops as kops
    s, t = stq[0], stq[1]
    with jax.named_scope("wcsd.emit_worklist"):
        qidx, stile, ttile = emit_ragged_worklist(
            tile_base, tile_cnt, s, t, worklist_len=worklist_len)
    with jax.named_scope("wcsd.join"):
        op = (kops.wcsd_profile_ragged_compressed if compressed
              else kops.wcsd_profile_ragged)
        out = op(hub, dist, wlev, tile_lo, tile_hi, qidx, stile, ttile,
                 num_rows=int(s.shape[0]) + 1, num_levels=num_levels,
                 interpret=interpret, use_kernel=use_kernel)
    with jax.named_scope("wcsd.unstage"):
        return out[: s.shape[0]]


class PendingResult:
    """Handle to an in-flight query batch.

    Device work is already dispatched when the handle is created; `wait()`
    materializes the answers on host (once — the handle caches). This is
    what lets `WCSDServer` overlap host-side planning of batch k+1 with
    device execution of batch k. ``deps`` are the in-flight device arrays
    the finalizer will read: `ready()` probes them without blocking, which
    is what lets the server dispatch opportunistically the moment the
    in-flight slot's device work finishes. Construction also starts each
    dep's device-to-host copy (`jax.Array.copy_to_host_async`), so the
    runtime moves the answers as soon as the program defines them instead
    of when `wait()` asks for them; deps without the method (readiness
    probes, host values) are left alone.

    ``deadline`` (absolute `time.monotonic()` seconds, or None) is stamped
    by the flush watchdog at dispatch: a handle past its deadline that is
    still not `ready()` is treated as wedged and abandoned — device work
    is not interruptible, so "cancel" means its result is never read and
    the SAME batch is re-dispatched (core/serve.py retry loop)."""

    def __init__(self, finalize, deps=()):
        self._finalize = finalize
        self._deps = tuple(deps)
        self._out = None
        self.deadline = None
        for d in self._deps:
            start_copy = getattr(d, "copy_to_host_async", None)
            if start_copy is not None:
                start_copy()

    def expired(self, now: float) -> bool:
        """True when a deadline is set, has passed, and the handle still
        is not ready — the watchdog's timeout predicate."""
        return (self.deadline is not None and now > self.deadline
                and not self.ready())

    def ready(self) -> bool:
        """Non-blocking: True once every declared device dependency has
        its data on host reach (so `wait()` would not block on the
        device). Handles with no declared deps — synchronous stubs, or
        already-waited handles — report ready."""
        if self._finalize is None:
            return True
        return all(d.is_ready() for d in self._deps
                   if hasattr(d, "is_ready"))

    def wait(self) -> np.ndarray:
        if self._finalize is not None:
            self._out = np.asarray(self._finalize())
            self._finalize = None
        return self._out


def _pad_sub_batch(slot_of, num_levels, pos, s, t, w_level, npad):
    """One planned sub-batch as a single [3, npad] staging array stacking
    (srow, trow, wq) — ONE H2D transfer instead of three; the device side
    unpacks in-jit (`ops.wcsd_query_segmented_staged`). Pads point at slot
    0 with query level num_levels + 1 — infeasible at any stored wlev, so
    pad lanes compute INF and are discarded."""
    n = len(pos)
    stq = np.zeros((3, npad), dtype=np.int32)
    stq[2, :] = num_levels + 1
    stq[0, :n] = slot_of[s[pos]]
    stq[1, :n] = slot_of[t[pos]]
    stq[2, :n] = w_level[pos]
    return stq


def _build_padded_store(idx, cap, lane_pad: bool):
    """[V, L] padded label arrays (+ lane padding for the Pallas kernel)."""
    h, d, w, c = idx.padded_device_arrays(cap)
    L = h.shape[1]
    Lp = round_to_lane(L) if lane_pad else L
    if Lp != L:
        pad = ((0, 0), (0, Lp - L))
        h = np.pad(h, pad, constant_values=-1)
        d = np.pad(d, pad, constant_values=INF_DIST)
        w = np.pad(w, pad, constant_values=-1)
    return h, d, w, c


class _QueryEngineBase:
    """Shared engine plumbing: the host-side bucket-pair plan / pad /
    dispatch / assemble loop of the CSR layout, and quality-threshold
    canonicalization. Subclasses provide ``_bucket_of`` / ``_slot_of`` /
    ``num_buckets`` / ``num_levels`` and a per-sub-batch dispatch.

    A `WCSDServer` hands every engine it builds its tracer and its
    `ServeStats` (``serve_stats``, for the ``new_programs`` count)."""

    tracer = tracing.OFF
    serve_stats = None

    def _plan_segmented(self, s, t, w_level, pad_len, dispatch
                        ) -> PendingResult:
        """Plan on host, dispatch each sub-batch (padded to ``pad_len(n)``,
        staged as one [3, npad] array) via ``dispatch(sub, stq)``;
        materialization of every sub-result is deferred to `wait()`."""
        s = np.asarray(s, np.int32)
        t = np.asarray(t, np.int32)
        w_level = np.asarray(w_level, np.int32)
        parts = []
        for sub in plan_query_batch(self._bucket_of, s, t,
                                    num_buckets=self.num_buckets):
            pos = sub.positions
            stq = _pad_sub_batch(self._slot_of, self.num_levels,
                                 pos, s, t, w_level, pad_len(len(pos)))
            parts.append((pos, dispatch(sub, stq)))

        def assemble():
            out = np.full(len(s), INF_DIST, dtype=np.int32)
            for pos, res in parts:
                out[pos] = np.asarray(res)[:len(pos)]
            return out
        return PendingResult(assemble, deps=[r for _, r in parts])

    def _plan_profile(self, s, t, pad_len, dispatch) -> PendingResult:
        """Profile variant of `_plan_segmented`: no per-query level — every
        level is answered by the one sweep — so the [2, npad] staging array
        carries only row ids (pads point at slot 0 and are sliced off on
        assembly) and assembly scatters [n, W + 1] staircases into the
        batch order."""
        s = np.asarray(s, np.int32)
        t = np.asarray(t, np.int32)
        parts = []
        for sub in plan_query_batch(self._bucket_of, s, t,
                                    num_buckets=self.num_buckets):
            pos = sub.positions
            n = len(pos)
            stq = np.zeros((2, pad_len(n)), dtype=np.int32)
            stq[0, :n] = self._slot_of[s[pos]]
            stq[1, :n] = self._slot_of[t[pos]]
            parts.append((pos, dispatch(sub, stq)))

        def assemble():
            out = np.full((len(s), self.num_levels + 1), INF_DIST,
                          dtype=np.int32)
            for pos, res in parts:
                out[pos] = np.asarray(res)[:len(pos)]
            return out
        return PendingResult(assemble, deps=[r for _, r in parts])

    # ----------------------------------------------------- ragged dispatch
    def _planned(self, t0: int, key: tuple) -> tuple[int, bool]:
        """After one ragged flush's host plan (begun at ``t0``): count
        ``key`` — (profile, padded Q, worklist length, gather capacity),
        every static the flush program is specialised on — as a new
        program the first time this engine meets it, and close the plan
        span. Returns (launch start, new)."""
        new = key not in self._shapes
        if new:
            self._shapes.add(key)
            if self.serve_stats is not None:
                self.serve_stats.new_programs += 1
        tr = self.tracer
        if not tr.on:
            return 0, new
        t1 = tracing.now()
        tr.span(tracing.PLAN, t0, t1)
        tr.shape(key[1], key[2])
        return t1, new

    def _launched(self, t1: int, new: bool) -> None:
        """After the device_put and jitted call of a flush begun at
        ``t1``; the first call of a new shape also traces and compiles."""
        tr = self.tracer
        if tr.on:
            t2 = tracing.now()
            tr.span(tracing.LAUNCH, t1, t2)
            if new:
                tr.span(tracing.BUILD, t1, t2)

    def _stage_ragged(self, s, t, w_level=None):
        """Staged query array for one ragged flush: queries padded by the
        engine's batch rule, stacked into one [3 or 2, Q] H2D staging
        array. Pad lanes use the arena's minimal-tile-count vertex at an
        infeasible level — a hub-heavy vertex 0 must not cost every pad
        lane its tile count squared in worklist items."""
        n = len(s)
        Q = self._ragged_pad(n)
        if w_level is not None:
            stq = np.full((3, Q), self._pad_vertex, dtype=np.int32)
            stq[2, :] = self.num_levels + 1
            stq[2, :n] = w_level
        else:
            stq = np.full((2, Q), self._pad_vertex, dtype=np.int32)
        stq[0, :n] = s
        stq[1, :n] = t
        return stq

    def query_from_quality(self, s, t, w: np.ndarray, levels: np.ndarray):
        """Real-valued thresholds -> levels (exact canonicalization)."""
        wl = np.searchsorted(levels, np.asarray(w), side="left")
        return self.query(s, t, wl.astype(np.int32))


class DeviceQueryEngine(_QueryEngineBase):
    """Holds device-resident labels and answers query batches.

    layout="padded": one [V, cap] store, every query pays the global-max
    label width (kernel: `wcsd_query_gathered`).
    layout="csr": the CSR-packed store, two dispatch modes:

      dispatch="ragged" (default): the whole batch — every bucket mix —
      runs as ONE kernel launch over the lane-tiled `LabelArena`; the
      batch plan is a device-emitted tile-pair worklist
      (`emit_ragged_worklist`), no host argsort/unique per flush.
      dispatch="bucket_pair": the original per-(bucket_s, bucket_t)
      dispatch loop (`plan_query_batch` + `wcsd_query_segmented`), kept as
      the ragged path's differential oracle.

    ``idx`` may be a padded `WCIndex` or a `PackedWCIndex` from the
    device-resident batched builder; for the latter the csr layout adopts
    the already-packed store as-is (`idx.packed()` is the store itself —
    no repack between construction and serving).

    ``interpret=None`` resolves via `kernels.ops.resolve_interpret`:
    compiled kernels on TPU (the only backend that lowers these Mosaic
    kernels), interpret emulation elsewhere or by explicit request.
    """

    def __init__(self, idx: WCIndex | PackedWCIndex, cap: int | None = None,
                 use_pallas: bool = False, interpret: bool | None = None,
                 layout: str = "padded", dispatch: str = "ragged",
                 lane: int | None = None, compressed: bool = False):
        from ..kernels.ops import resolve_interpret
        if layout not in ("padded", "csr"):
            raise ValueError(f"unknown layout: {layout!r}")
        if dispatch not in ("ragged", "bucket_pair"):
            raise ValueError(f"unknown dispatch: {dispatch!r}")
        if layout == "csr" and cap is not None:
            raise ValueError("cap (label-row trimming) only applies to the "
                             "padded layout; the CSR store keeps exact rows")
        if compressed and (layout, dispatch) != ("csr", "ragged"):
            raise ValueError("compressed=True requires layout='csr' with "
                             "dispatch='ragged' (only the arena megakernel "
                             "decodes the compressed tile format)")
        self.layout = layout
        self.use_pallas = use_pallas
        self.interpret = resolve_interpret(interpret)
        self.num_levels = idx.num_levels
        self.compressed = False
        self.compression_overflow = False
        self._shapes: set = set()   # ragged flush shapes dispatched
        if layout == "csr":
            from .wc_index import LANE
            lane = LANE if lane is None else int(lane)
            packed = idx.packed(lane=lane)
            self.packed = packed
            self.dispatch = dispatch
            self._bucket_of = packed.bucket_of
            self._slot_of = packed.slot_of
            self.num_buckets = packed.num_buckets
            if dispatch == "ragged":
                ar = packed.arena(lane=lane)
                self._tile_cnt_np = ar.tile_cnt
                self._pad_vertex = int(np.argmin(ar.tile_cnt))
                src = ar
                if compressed:
                    comp = packed.compressed_arena(lane=lane)
                    if comp.num_overflow_tiles:
                        # the store does not fit the compressed format
                        # losslessly (hub-delta / level / distance range
                        # overflow) — serve uncompressed and say so rather
                        # than silently corrupting answers
                        self.compression_overflow = True
                    else:
                        self.compressed = True
                        src = comp
                trio = (pad_group_rows(src.hub_delta, src.dist, src.wlev)
                        if self.compressed else (src.hub, src.dist, src.wlev))
                self._arena = tuple(jnp.asarray(a) for a in trio + (
                    src.tile_lo, src.tile_hi, src.tile_base, src.tile_cnt))
            else:
                self._tiles = [tuple(jnp.asarray(a)
                                     for a in packed.bucket_tiles(b))
                               for b in range(packed.num_buckets)]
            return
        self.dispatch = "dense"
        h, d, w, c = _build_padded_store(idx, cap, lane_pad=use_pallas)
        self.hub = jnp.asarray(h)
        self.dist = jnp.asarray(d)
        self.wlev = jnp.asarray(w)
        self.count = jnp.asarray(c)

    def query(self, s, t, w_level) -> jax.Array:
        if self.layout == "csr":
            return jnp.asarray(self.query_async(s, t, w_level).wait())
        # dense path: hand back the dispatched device array directly — no
        # host round trip for callers that keep computing on device
        return self._query_dense(s, t, w_level)

    def query_async(self, s, t, w_level) -> PendingResult:
        """Dispatch a batch without materializing answers: host planning is
        done and every device call issued when this returns; `wait()` on
        the handle syncs."""
        if self.layout == "csr":
            if self.dispatch == "ragged":
                return self._query_ragged_async(s, t, w_level)
            return self._query_segmented_async(s, t, w_level)
        res = self._query_dense(s, t, w_level)
        return PendingResult(lambda: res, deps=(res,))

    def _query_dense(self, s, t, w_level) -> jax.Array:
        s = jnp.asarray(s, jnp.int32)
        t = jnp.asarray(t, jnp.int32)
        w_level = jnp.asarray(w_level, jnp.int32)
        if self.use_pallas:
            from ..kernels import ops as kops
            return kops.wcsd_query(self.hub, self.dist, self.wlev, self.count,
                                   s, t, w_level, interpret=self.interpret)
        return query_batch_jnp(self.hub, self.dist, self.wlev, self.count,
                               s, t, w_level)

    _ragged_pad = staticmethod(round_to_pow2)

    def _query_ragged_async(self, s, t, w_level) -> PendingResult:
        t0 = tracing.now() if self.tracer.on else 0
        s = np.asarray(s, np.int32)
        t = np.asarray(t, np.int32)
        w_level = np.asarray(w_level, np.int32)
        n = len(s)
        stq = self._stage_ragged(s, t, w_level)
        wl_len = ragged_worklist_len(self._tile_cnt_np, stq[0], stq[1])
        t1, new = self._planned(t0, (False, stq.shape[1], wl_len, None))
        res = ragged_query_batch(*self._arena, jnp.asarray(stq),
                                 worklist_len=wl_len,
                                 interpret=self.interpret,
                                 use_kernel=self.use_pallas,
                                 compressed=self.compressed)
        self._launched(t1, new)
        return PendingResult(lambda: np.asarray(res)[:n], deps=(res,))

    def _query_segmented_async(self, s, t, w_level) -> PendingResult:
        from ..kernels import ops as kops

        def dispatch(sub, stq):
            hs, ds, ws = self._tiles[sub.bucket_s]
            ht, dt, wt = self._tiles[sub.bucket_t]
            return kops.wcsd_query_segmented_staged(
                hs, ds, ws, ht, dt, wt, jnp.asarray(stq),
                interpret=self.interpret, use_kernel=self.use_pallas)

        # pad sub-batches to the next power of two: the compiled kernel
        # count stays O(buckets^2 * log B) instead of one per batch size
        return self._plan_segmented(s, t, w_level, round_to_pow2, dispatch)

    # ------------------------------------------------------------- profiles
    def query_profile(self, s, t) -> np.ndarray:
        """[B, W + 1] staircases: ``out[b, w] == query(s, t, w)[b]`` for
        every level in one label sweep (see `_staircase_from_rows`)."""
        if self.layout == "csr":
            return self.query_profile_async(s, t).wait()
        return np.asarray(self._profile_dense(s, t))

    def query_profile_async(self, s, t) -> PendingResult:
        if self.layout == "csr":
            if self.dispatch == "ragged":
                return self._profile_ragged_async(s, t)
            return self._profile_segmented_async(s, t)
        res = self._profile_dense(s, t)
        return PendingResult(lambda: res, deps=(res,))

    def _profile_dense(self, s, t) -> jax.Array:
        # the padded layout profiles on the XLA path for either kernel
        # setting: the one-sweep win is the single gather + fused min-scan,
        # which XLA already gives the dense store
        s = jnp.asarray(s, jnp.int32)
        t = jnp.asarray(t, jnp.int32)
        return profile_batch_jnp(self.hub, self.dist, self.wlev, self.count,
                                 s, t, num_levels=self.num_levels)

    def _profile_ragged_async(self, s, t) -> PendingResult:
        t0 = tracing.now() if self.tracer.on else 0
        s = np.asarray(s, np.int32)
        t = np.asarray(t, np.int32)
        n = len(s)
        stq = self._stage_ragged(s, t)
        wl_len = ragged_worklist_len(self._tile_cnt_np, stq[0], stq[1])
        t1, new = self._planned(t0, (True, stq.shape[1], wl_len, None))
        res = ragged_profile_batch(*self._arena, jnp.asarray(stq),
                                   worklist_len=wl_len,
                                   num_levels=self.num_levels,
                                   interpret=self.interpret,
                                   use_kernel=self.use_pallas,
                                   compressed=self.compressed)
        self._launched(t1, new)
        return PendingResult(lambda: np.asarray(res)[:n], deps=(res,))

    def _profile_segmented_async(self, s, t) -> PendingResult:
        from ..kernels import ops as kops

        def dispatch(sub, stq):
            hs, ds, ws = self._tiles[sub.bucket_s]
            ht, dt, wt = self._tiles[sub.bucket_t]
            return kops.wcsd_profile_segmented_staged(
                hs, ds, ws, ht, dt, wt, jnp.asarray(stq),
                num_levels=self.num_levels,
                interpret=self.interpret, use_kernel=self.use_pallas)

        return self._plan_profile(s, t, round_to_pow2, dispatch)


class ShardedQueryEngine(_QueryEngineBase):
    """Multi-device serving engine: the label store on a mesh, the query
    batch sharded over its ("pod",) "data" axes.

    Two placements, chosen by a per-device HBM budget:

    mode="replicated" (default): every device holds the full label store
    (`NamedSharding` with an all-`None` spec) and answers its slice of the
    batch under `shard_map` — zero per-query communication, linear
    throughput scaling. layout="csr" defaults to the ragged megakernel
    (dispatch="ragged"): the arena is replicated, the staged batch splits
    over the mesh, and each device emits + launches the worklist of its
    own slice — one kernel launch per device per flush, no host planner.
    dispatch="bucket_pair" keeps the host-side planner: each planned
    sub-batch is padded to a device multiple and the segmented
    scalar-prefetch kernel runs inside `shard_map`.

    mode="sharded_labels": when the store exceeds ``device_budget_bytes``,
    the label store shards its vertex/tile-row axis over the same devices
    in contiguous blocks. Query row ids are replicated; each device
    contributes its owned label rows and one reduce-scatter
    (`distributed.collectives`) hands every device exactly the gathered
    rows of its own batch slice — only touched rows cross the
    interconnect, and each crosses it once. dispatch="ragged" keeps the
    megakernel in this mode too: every device emits the ragged worklist
    of its own batch slice, ONE fused reduce-scatter
    (`ragged_tile_gather`) delivers the worklist's arena tiles to their
    consuming device, and the one-per-device ragged launch joins the
    gathered tiles — a flush is one kernel launch per device plus one
    collective, and `use_pallas` / `interpret` route through
    `kernels.ops` exactly as in replicated mode. dispatch="bucket_pair"
    keeps the per-bucket row-gather loop as the differential oracle.

    ``compressed=True`` (csr + ragged only) serves from the
    `CompressedArena` — bf16 distances, delta-coded int16 hub ids, int8
    levels, decoded in-kernel — roughly 2.4x the rows per device under
    the same ``device_budget_bytes``. Hub ids and levels are exact; see
    `CompressedArena` for the documented distance error bound. Stores
    whose deltas/levels overflow the compressed format fall back to the
    uncompressed arena with ``compression_overflow = True``.

    Every query is answered by per-query integer min-plus reductions that
    no partitioning reorders, so results are bit-for-bit identical to
    `DeviceQueryEngine` on the same index (exactly, when uncompressed;
    within the documented distance bound when compressed).
    """

    def __init__(self, idx: WCIndex | PackedWCIndex, mesh=None,
                 cap: int | None = None, use_pallas: bool = False,
                 interpret: bool | None = None, layout: str = "csr",
                 device_budget_bytes: int | None = None,
                 multi_pod: bool = False, dispatch: str = "ragged",
                 lane: int | None = None, compressed: bool = False):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..kernels.ops import resolve_interpret

        if layout not in ("padded", "csr"):
            raise ValueError(f"unknown layout: {layout!r}")
        if dispatch not in ("ragged", "bucket_pair"):
            raise ValueError(f"unknown dispatch: {dispatch!r}")
        if layout == "csr" and cap is not None:
            raise ValueError("cap (label-row trimming) only applies to the "
                             "padded layout; the CSR store keeps exact rows")
        if mesh is None:
            from ..launch.mesh import make_serving_mesh
            mesh = make_serving_mesh(multi_pod=multi_pod)
        self.mesh = mesh
        self.batch_axes = tuple(a for a in mesh.axis_names
                                if a in ("pod", "data"))
        if not self.batch_axes:
            raise ValueError(f"mesh axes {mesh.axis_names} carry no "
                             "('pod', 'data') batch axis")
        self.ndev = int(np.prod([mesh.shape[a] for a in self.batch_axes]))
        self.layout = layout
        self.use_pallas = use_pallas
        self.interpret = resolve_interpret(interpret)
        self.num_levels = idx.num_levels
        self._P = P
        self._qspec = P(self.batch_axes)
        self._qsharding = NamedSharding(mesh, self._qspec)
        # sharded_labels mode wants the query ids replicated: every shard
        # scores the full row-id list and a reduce-scatter hands each its
        # own batch slice of the gathered rows
        self._qreplicated = NamedSharding(mesh, P(None))
        self._fns: dict = {}  # jitted shard_map callables, one per path
        self._shapes: set = set()   # ragged flush shapes dispatched

        if compressed and (layout, dispatch) != ("csr", "ragged"):
            raise ValueError("compressed=True requires layout='csr' with "
                             "dispatch='ragged' (only the arena megakernel "
                             "decodes the compressed tile format)")
        self.compressed = False
        self.compression_overflow = False
        if layout == "csr":
            from .wc_index import LANE
            lane = LANE if lane is None else int(lane)
            packed = idx.packed(lane=lane)
            self.packed = packed
            self._bucket_of = packed.bucket_of
            self._slot_of = packed.slot_of
            self.num_buckets = packed.num_buckets
            if dispatch == "ragged":
                ar = packed.arena(lane=lane)
                src = ar
                if compressed:
                    comp = packed.compressed_arena(lane=lane)
                    if comp.num_overflow_tiles:
                        # lossless fallback: the store overflows the
                        # compressed cell ranges, serve uncompressed
                        self.compression_overflow = True
                    else:
                        self.compressed = True
                        src = comp
                # the mode decision sees the bytes the chosen arena
                # actually costs — compression raises the row count a
                # fixed budget admits before sharding kicks in
                self.store_bytes_per_device = src.memory_bytes()
            else:
                self.store_bytes_per_device = packed.tile_memory_bytes()
        else:
            h, d, w, c = _build_padded_store(idx, cap, lane_pad=use_pallas)
            self.store_bytes_per_device = int(
                h.nbytes + d.nbytes + w.nbytes + c.nbytes)
        self.mode = ("replicated"
                     if device_budget_bytes is None
                     or self.store_bytes_per_device <= device_budget_bytes
                     else "sharded_labels")
        if self.mode == "sharded_labels":
            self.store_bytes_per_device = ceil_to(
                self.store_bytes_per_device, self.ndev) // self.ndev
        # the csr layout keeps the requested dispatch in BOTH placements:
        # row-sharded ragged routes each device's worklist tiles to their
        # consumer with one fused reduce-scatter (`ragged_tile_gather`).
        # The padded layout has no dispatch choice (one store, one path).
        self.dispatch = dispatch if layout == "csr" else "dense"

        rep = NamedSharding(mesh, P(*(None, None)))
        if layout == "csr":
            if self.dispatch == "ragged":
                self._tile_cnt_np = ar.tile_cnt
                self._tile_base_np = ar.tile_base
                self._num_tiles_np = int(ar.num_tiles)
                self._pad_vertex = int(np.argmin(ar.tile_cnt))
                trio = ((src.hub_delta, src.dist, src.wlev)
                        if self.compressed else (src.hub, src.dist, src.wlev))
                rest = (src.tile_lo, src.tile_hi, src.tile_base, src.tile_cnt)
                rep1 = NamedSharding(mesh, P(None))
                if self.mode == "sharded_labels":
                    trio = self._shard_arena_tiles(trio)
                else:
                    if self.compressed:
                        trio = pad_group_rows(*trio)
                    trio = tuple(jax.device_put(a, rep) for a in trio)
                self._arena = trio + tuple(jax.device_put(a, rep1)
                                           for a in rest)
            else:
                self._tiles = []
                for b in range(packed.num_buckets):
                    tiles = packed.bucket_tiles(b)
                    if self.mode == "sharded_labels":
                        tiles = self._shard_tile_rows(tiles)
                    else:
                        tiles = tuple(jax.device_put(a, rep) for a in tiles)
                    self._tiles.append(tiles)
        elif self.mode == "sharded_labels":
            (self.hub, self.dist, self.wlev), self.count, self._rows_per = \
                self._shard_store_rows((h, d, w), c)
        else:
            crep = NamedSharding(mesh, P(None))
            self.hub = jax.device_put(h, rep)
            self.dist = jax.device_put(d, rep)
            self.wlev = jax.device_put(w, rep)
            self.count = jax.device_put(c, crep)

    # ------------------------------------------------------------ placement
    def _shard_tile_rows(self, tiles):
        """Pad a bucket tile's row count to a device multiple (standard pad
        contract) and shard the row axis over the batch axes."""
        from jax.sharding import NamedSharding
        h, d, w = tiles
        n = h.shape[0]
        npad = ceil_to(max(n, 1), self.ndev)
        if npad != n:
            h = np.pad(h, ((0, npad - n), (0, 0)), constant_values=-1)
            d = np.pad(d, ((0, npad - n), (0, 0)), constant_values=INF_DIST)
            w = np.pad(w, ((0, npad - n), (0, 0)), constant_values=-1)
        sh = NamedSharding(self.mesh, self._P(self.batch_axes, None))
        return tuple(jax.device_put(a, sh) for a in (h, d, w))

    def _shard_arena_tiles(self, trio):
        """Pad the arena trio's tile-row axis to a device multiple (pad
        tiles carry the standard pad contract and are never named by any
        worklist — tile_base/tile_cnt only address real tiles) and shard
        it over the batch axes; records the per-device block height for
        the worklist tile gather."""
        from jax.sharding import NamedSharding
        h, d, w = trio
        T = h.shape[0]
        Tpad = ceil_to(max(T, 1), self.ndev)
        self._tiles_per = Tpad // self.ndev
        if Tpad != T:
            pad = ((0, Tpad - T), (0, 0))
            dfill = INF_DIST if d.dtype == np.int32 else np.inf
            h = np.pad(h, pad, constant_values=-1)
            d = np.pad(d, pad, constant_values=dfill)
            w = np.pad(w, pad, constant_values=-1)
        sh = NamedSharding(self.mesh, self._P(self.batch_axes, None))
        return tuple(jax.device_put(a, sh) for a in (h, d, w))

    def _shard_store_rows(self, arrays, count):
        """Pad the padded store's vertex axis to a device multiple and
        shard it; returns (sharded arrays, sharded count, rows/device)."""
        from jax.sharding import NamedSharding
        V = arrays[0].shape[0]
        Vp = ceil_to(V, self.ndev)
        fills = (-1, INF_DIST, -1)
        if Vp != V:
            arrays = tuple(np.pad(a, ((0, Vp - V), (0, 0)),
                                  constant_values=f)
                           for a, f in zip(arrays, fills))
            count = np.pad(count, (0, Vp - V))
        sh2 = NamedSharding(self.mesh, self._P(self.batch_axes, None))
        sh1 = NamedSharding(self.mesh, self._P(self.batch_axes))
        return (tuple(jax.device_put(a, sh2) for a in arrays),
                jax.device_put(count, sh1), Vp // self.ndev)

    # -------------------------------------------------------------- queries
    def query(self, s, t, w_level) -> jax.Array:
        if self.layout == "csr":
            return jnp.asarray(self.query_async(s, t, w_level).wait())
        # dense path: hand back the (sharded) device array directly
        res, n = self._dispatch_padded(s, t, w_level)
        return res[:n]

    def query_async(self, s, t, w_level) -> PendingResult:
        s = np.asarray(s, np.int32)
        t = np.asarray(t, np.int32)
        w_level = np.asarray(w_level, np.int32)
        if self.layout == "csr":
            return self._query_csr_async(s, t, w_level)
        res, n = self._dispatch_padded(s, t, w_level)
        return PendingResult(lambda: np.asarray(res)[:n], deps=(res,))

    def _batch_pad(self, n: int) -> int:
        """Power-of-two batch padding, rounded up to a device multiple so
        shard_map can split the batch axis evenly."""
        return ceil_to(max(round_to_pow2(n), self.ndev), self.ndev)

    def _put_queries(self, *arrays):
        sh = (self._qreplicated if self.mode == "sharded_labels"
              else self._qsharding)
        return (jax.device_put(a, sh) for a in arrays)

    def _put_staged(self, stq):
        """Place one [k, npad] staging array: the query axis (axis 1)
        sharded over the batch axes in replicated mode, fully replicated
        in sharded_labels mode (every shard scores the full row-id list)."""
        from jax.sharding import NamedSharding
        spec = (self._P(None, None) if self.mode == "sharded_labels"
                else self._P(None, self.batch_axes))
        return jax.device_put(stq, NamedSharding(self.mesh, spec))

    # ---- padded layout
    def _dispatch_padded(self, s, t, w_level):
        """Dispatch one dense batch; returns (device result [npad], n)."""
        s = np.asarray(s, np.int32)
        t = np.asarray(t, np.int32)
        w_level = np.asarray(w_level, np.int32)
        n = len(s)
        npad = self._batch_pad(n)
        sp = np.zeros(npad, dtype=np.int32)
        tp = np.zeros(npad, dtype=np.int32)
        wp = np.full(npad, self.num_levels + 1, dtype=np.int32)  # infeasible
        sp[:n], tp[:n], wp[:n] = s, t, w_level
        fn = self._padded_fn()
        return fn(self.hub, self.dist, self.wlev, self.count,
                  *self._put_queries(sp, tp, wp)), n

    def _padded_fn(self):
        key = ("padded", self.mode)
        if key in self._fns:
            return self._fns[key]
        P, q = self._P, self._qspec
        if self.mode == "replicated":
            use_pallas, interpret = self.use_pallas, self.interpret

            def local(hub, dist, wlev, count, s, t, wq):
                if use_pallas:
                    from ..kernels import ops as kops
                    return kops.wcsd_query(hub, dist, wlev, count, s, t, wq,
                                           interpret=interpret)
                return query_batch_jnp(hub, dist, wlev, count, s, t, wq)

            in_specs = (P(None, None),) * 3 + (P(None),) + (q,) * 3
        else:
            axes, rows_per, ndev = self.batch_axes, self._rows_per, self.ndev

            def local(hub, dist, wlev, count, s, t, wq):
                # s/t/wq arrive REPLICATED: every shard scores the full
                # row-id list against its row block and a reduce-scatter
                # leaves each shard the gathered rows of its batch slice
                from ..distributed.collectives import (
                    batch_slice, row_gather_psum_scatter)
                wq_loc = batch_slice(wq, axes, s.shape[0] // ndev)

                def side(v):
                    h = row_gather_psum_scatter(hub, v, axes, rows_per)
                    dd = row_gather_psum_scatter(dist, v, axes, rows_per)
                    ww = row_gather_psum_scatter(wlev, v, axes, rows_per)
                    cc = row_gather_psum_scatter(count, v, axes, rows_per)
                    col = jnp.arange(h.shape[1])
                    m = (col[None, :] < cc[:, None]) & (ww >= wq_loc[:, None])
                    return h, jnp.where(m, jnp.minimum(dd, DEV_INF), DEV_INF)

                hs, ds = side(s)
                ht, dt = side(t)
                eq = hs[:, :, None] == ht[:, None, :]
                best = jnp.where(eq, ds[:, :, None] + dt[:, None, :],
                                 DEV_INF).min(axis=(1, 2))
                return jnp.where(best >= DEV_INF, INF_DIST,
                                 best).astype(jnp.int32)

            in_specs = (P(self.batch_axes, None),) * 3 \
                + (P(self.batch_axes),) + (P(None),) * 3
        fn = jax.jit(jax.shard_map(local, mesh=self.mesh, in_specs=in_specs,
                                  out_specs=q, check_vma=False))
        self._fns[key] = fn
        return fn

    # ---- csr layout
    def _query_csr_async(self, s, t, w_level) -> PendingResult:
        if self.dispatch == "ragged":
            return self._query_ragged_async(s, t, w_level)
        fn = self._segmented_fn()

        def dispatch(sub, stq):
            hs, ds, ws = self._tiles[sub.bucket_s]
            ht, dt, wt = self._tiles[sub.bucket_t]
            return fn(hs, ds, ws, ht, dt, wt, self._put_staged(stq))

        return self._plan_segmented(s, t, w_level, self._batch_pad, dispatch)

    def _ragged_pad(self, n: int) -> int:
        return self._batch_pad(n)

    def _shard_worklist_len(self, stq) -> int:
        """Per-shard worklist capacity: each shard plans its own contiguous
        batch slice inside shard_map, so the static capacity is the max
        over shards' tile-pair totals."""
        b_loc = stq.shape[1] // self.ndev
        return max(ragged_worklist_len(
            self._tile_cnt_np, stq[0, k * b_loc:(k + 1) * b_loc],
            stq[1, k * b_loc:(k + 1) * b_loc]) for k in range(self.ndev))

    def _balance_ragged(self, stq):
        """Load-balanced device assignment for the row-sharded flush: hot
        queries usually arrive clustered (one tenant, one hot subgraph),
        and the static per-shard worklist capacity is the MAX over device
        slices — one heavy contiguous slice makes every device pay its
        worklist. Queries are dealt in descending tile-pair cost, each
        round handing the heaviest remaining queries to the least-loaded
        devices (capacity-constrained LPT: every device gets exactly
        npad/ndev), so the capacity tracks the batch mean instead.
        Returns (stq reordered device-major, perm); results are
        unpermuted with ``out[perm] = res``."""
        ndev = self.ndev
        if ndev == 1:
            return stq, np.arange(stq.shape[1])
        tc = self._tile_cnt_np
        c = tc[stq[0]].astype(np.int64) * tc[stq[1]]
        order = np.argsort(-c, kind="stable")
        b = stq.shape[1] // ndev
        load = np.zeros(ndev, np.int64)
        perm = np.empty(stq.shape[1], np.int64)
        cs = c[order].reshape(b, ndev)
        ob = order.reshape(b, ndev)
        for blk in range(b):
            dst = np.argsort(load, kind="stable")
            perm[dst * b + blk] = ob[blk]
            load[dst] += cs[blk]
        return stq[:, perm], perm

    def _balanced_worklist_len(self, stq) -> int:
        """Per-shard worklist capacity for a BALANCED flush: slice totals
        sit near the batch mean, so capacity rounds to the next
        512-multiple (not the next power of two — doubling a balanced
        slice's capacity would hand every device back the pad waste the
        balancing just removed)."""
        b = stq.shape[1] // self.ndev
        tc = self._tile_cnt_np
        tot = max(int(tc[stq[0, k * b:(k + 1) * b]].astype(np.int64)
                      @ tc[stq[1, k * b:(k + 1) * b]])
                  for k in range(self.ndev))
        return ceil_to(max(tot, 1), 512)

    def _gather_plan(self, stq, worklist_len: int):
        """Host-side gather plan for the row-sharded arena: per device, the
        sorted DISTINCT arena tiles its batch slice can name — the union of
        the slice vertices' tile ranges, NOT the worklist (a hub-heavy row
        joined by a thousand queries still contributes its tiles once).
        Rows are padded to a static capacity G with the last real tile id
        (keeps the array sorted for the device-side binary search); G is
        rounded up to a 256-multiple so the compiled-shape count stays
        small. O(B + tiles named) numpy, the same order of host work as
        `ragged_worklist_len`."""
        ndev = self.ndev
        b = stq.shape[1] // ndev
        tb, tc = self._tile_base_np, self._tile_cnt_np
        uniqs = []
        for k in range(ndev):
            v = np.unique(np.concatenate([stq[0, k * b:(k + 1) * b],
                                          stq[1, k * b:(k + 1) * b]]))
            cnt = tc[v].astype(np.int64)
            # expand the [tb[v], tb[v] + tc[v]) ranges vectorized
            ends = np.cumsum(cnt)
            idx = np.arange(int(ends[-1]))
            own = np.searchsorted(ends, idx, side="right")
            uniqs.append(np.unique(
                tb[v][own] + (idx - (ends[own] - cnt[own]))).astype(np.int32))
        G = ceil_to(max(len(u) for u in uniqs), 256)
        uniq = np.full((ndev, G), self._num_tiles_np - 1, dtype=np.int32)
        for k, u in enumerate(uniqs):
            uniq[k, :len(u)] = u
        return uniq, G

    def _query_ragged_async(self, s, t, w_level) -> PendingResult:
        t0 = tracing.now() if self.tracer.on else 0
        n = len(s)
        stq = self._stage_ragged(s, t, w_level)
        if self.mode == "sharded_labels":
            stq, perm = self._balance_ragged(stq)
            wl_len = self._balanced_worklist_len(stq)
            uniq, G = self._gather_plan(stq, wl_len)
            t1, new = self._planned(t0, (False, stq.shape[1], wl_len, G))
            fn = self._ragged_fn(wl_len, profile=False, gather_cap=G)
            res = fn(*self._arena, self._put_staged(stq),
                     self._put_staged(uniq))
            self._launched(t1, new)

            def finalize():
                out = np.empty(stq.shape[1], dtype=np.int32)
                out[perm] = np.asarray(res)
                return out[:n]

            return PendingResult(finalize, deps=(res,))
        wl_len = self._shard_worklist_len(stq)
        t1, new = self._planned(t0, (False, stq.shape[1], wl_len, None))
        fn = self._ragged_fn(wl_len, profile=False)
        res = fn(*self._arena, self._put_staged(stq))
        self._launched(t1, new)
        return PendingResult(lambda: np.asarray(res)[:n], deps=(res,))

    def _ragged_fn(self, worklist_len: int, profile: bool,
                   gather_cap: int | None = None):
        """Jitted shard_map over the ragged megakernel path.

        Replicated mode: the arena on every device, the staged batch split
        over the batch axes, each shard emitting + launching its own
        slice's worklist — one kernel launch per device per flush.

        Sharded-labels mode: the [T, lane] trio is tile-row-sharded, the
        staged batch load-balanced on host (`_balance_ragged`) and
        replicated alongside the host `_gather_plan` — per device, the
        sorted DISTINCT tiles its batch slice can name. ONE fused
        reduce-scatter (`ragged_tile_gather`) hands device k exactly
        those tiles, each crossing the interconnect once however many
        worklist entries name it (a hub-heavy row can be joined by
        thousands of queries in a flush). Each device then emits only its
        OWN slice's worklist (`emit_ragged_worklist`, no cross-device
        work), relabels it into the gathered buffer by binary search, and
        the same ragged launch joins it against the batch slice. A flush
        is one kernel launch per device plus one collective, with
        `use_pallas` / `interpret` routing through `kernels.ops` exactly
        as in replicated mode."""
        key = ("csr-ragged", self.mode, profile, worklist_len, gather_cap)
        if key in self._fns:
            return self._fns[key]
        P, q = self._P, self._qspec
        use_pallas, interpret = self.use_pallas, self.interpret
        compressed = self.compressed
        W = self.num_levels

        if self.mode == "replicated":
            if profile:
                def local(hub, dist, wlev, lo, hi, tbase, tcnt, stq):
                    return ragged_profile_batch(
                        hub, dist, wlev, lo, hi, tbase, tcnt, stq,
                        worklist_len=worklist_len, num_levels=W,
                        interpret=interpret, use_kernel=use_pallas,
                        compressed=compressed)
            else:
                def local(hub, dist, wlev, lo, hi, tbase, tcnt, stq):
                    return ragged_query_batch(
                        hub, dist, wlev, lo, hi, tbase, tcnt, stq,
                        worklist_len=worklist_len,
                        interpret=interpret, use_kernel=use_pallas,
                        compressed=compressed)

            in_specs = (P(None, None),) * 3 + (P(None),) * 4 \
                + (P(None, self.batch_axes),)
        else:
            axes, ndev = self.batch_axes, self.ndev
            tiles_per, WL = self._tiles_per, worklist_len

            def local(hub, dist, wlev, lo, hi, tbase, tcnt, stq, uniq):
                from ..distributed.collectives import (axis_linear_index,
                                                       ragged_tile_gather)
                from ..kernels import ops as kops
                b = stq.shape[1] // ndev
                me = axis_linear_index(axes)

                def mine(a):
                    return jax.lax.dynamic_slice_in_dim(a, me * b, b)

                with jax.named_scope("wcsd.gather"):
                    # one fused reduce-scatter routes each device's
                    # host-planned DISTINCT tile list to it, in linear
                    # device order — each tile crosses the interconnect
                    # once
                    gh, gd, gw = ragged_tile_gather(
                        (hub, dist, wlev), uniq.reshape(-1), axes,
                        tiles_per)
                with jax.named_scope("wcsd.emit_worklist"):
                    qidx, stile, ttile = emit_ragged_worklist(
                        tbase, tcnt, mine(stq[0]), mine(stq[1]),
                        worklist_len=WL)
                    # relabel worklist tiles into the gathered buffer: the
                    # plan rows are sorted (fill = last real tile id), so
                    # a binary search lands every real entry; worklist
                    # pads name tile 0, whose probe row is trash-routed
                    uniq_me = jax.lax.dynamic_index_in_dim(
                        uniq, me, axis=0, keepdims=False)
                    sloc = jnp.searchsorted(uniq_me, stile).astype(jnp.int32)
                    tloc = jnp.searchsorted(uniq_me, ttile).astype(jnp.int32)
                with jax.named_scope("wcsd.join"):
                    args = (gh, gd, gw, lo[uniq_me], hi[uniq_me], qidx,
                            sloc, tloc)
                    if profile:
                        op = (kops.wcsd_profile_ragged_compressed
                              if compressed else kops.wcsd_profile_ragged)
                        out = op(*args, num_rows=b + 1, num_levels=W,
                                 interpret=interpret, use_kernel=use_pallas)
                    else:
                        wq = jnp.concatenate([
                            mine(stq[2]),
                            jnp.full((1,), 1 << 20, jnp.int32)])
                        op = (kops.wcsd_query_ragged_compressed
                              if compressed else kops.wcsd_query_ragged)
                        out = op(*args, wq, interpret=interpret,
                                 use_kernel=use_pallas)
                with jax.named_scope("wcsd.unstage"):
                    return out[:b]

            in_specs = (P(self.batch_axes, None),) * 3 + (P(None),) * 4 \
                + (P(None, None), P(None, None))
        fn = jax.jit(jax.shard_map(local, mesh=self.mesh, in_specs=in_specs,
                                  out_specs=q, check_vma=False))
        self._fns[key] = fn
        return fn

    def _segmented_fn(self):
        key = ("csr", self.mode)
        if key in self._fns:
            return self._fns[key]
        P, q = self._P, self._qspec
        if self.mode == "replicated":
            use_pallas, interpret = self.use_pallas, self.interpret

            def local(hs, ds, ws, ht, dt, wt, stq):
                from ..kernels import ops as kops
                return kops.wcsd_query_segmented_staged(
                    hs, ds, ws, ht, dt, wt, stq,
                    interpret=interpret, use_kernel=use_pallas)

            tile = P(None, None)
            qspec = P(None, self.batch_axes)
        else:
            axes, ndev = self.batch_axes, self.ndev

            def local(hs, ds, ws, ht, dt, wt, stq):
                # replicated row ids + reduce-scatter, as in the padded
                # sharded-labels path; tiles are row-sharded per bucket
                from ..distributed.collectives import (
                    batch_slice, row_gather_psum_scatter)
                srow, trow, wq = stq[0], stq[1], stq[2]
                wq_loc = batch_slice(wq, axes, srow.shape[0] // ndev)

                def side(h, d, w, rows):
                    per = h.shape[0]  # local row-block height
                    hg = row_gather_psum_scatter(h, rows, axes, per)
                    dg = row_gather_psum_scatter(d, rows, axes, per)
                    wg = row_gather_psum_scatter(w, rows, axes, per)
                    # store pads carry wlev = -1: one compare masks both
                    # out-of-row and infeasible entries
                    return hg, jnp.where(wg >= wq_loc[:, None],
                                         jnp.minimum(dg, DEV_INF), DEV_INF)

                hs2, ds2 = side(hs, ds, ws, srow)
                ht2, dt2 = side(ht, dt, wt, trow)
                eq = hs2[:, :, None] == ht2[:, None, :]
                best = jnp.where(eq, ds2[:, :, None] + dt2[:, None, :],
                                 DEV_INF).min(axis=(1, 2))
                return jnp.where(best >= DEV_INF, INF_DIST,
                                 best).astype(jnp.int32)

            tile = P(self.batch_axes, None)
            qspec = P(None, None)
        in_specs = (tile,) * 6 + (qspec,)
        fn = jax.jit(jax.shard_map(local, mesh=self.mesh, in_specs=in_specs,
                                  out_specs=q, check_vma=False))
        self._fns[key] = fn
        return fn

    # ------------------------------------------------------------- profiles
    def query_profile(self, s, t) -> np.ndarray:
        """[B, W + 1] staircases, bit-identical to `DeviceQueryEngine.
        query_profile` on the same index (same per-query integer min-scan,
        only the batch placement differs)."""
        return self.query_profile_async(s, t).wait()

    def query_profile_async(self, s, t) -> PendingResult:
        s = np.asarray(s, np.int32)
        t = np.asarray(t, np.int32)
        if self.layout == "csr":
            if self.dispatch == "ragged":
                return self._profile_ragged_async(s, t)
            fn = self._profile_segmented_fn()

            def dispatch(sub, stq):
                hs, ds, ws = self._tiles[sub.bucket_s]
                ht, dt, wt = self._tiles[sub.bucket_t]
                return fn(hs, ds, ws, ht, dt, wt, self._put_staged(stq))

            return self._plan_profile(s, t, self._batch_pad, dispatch)
        res, n = self._dispatch_padded_profile(s, t)
        return PendingResult(lambda: np.asarray(res)[:n], deps=(res,))

    def _profile_ragged_async(self, s, t) -> PendingResult:
        t0 = tracing.now() if self.tracer.on else 0
        n = len(s)
        stq = self._stage_ragged(s, t)
        if self.mode == "sharded_labels":
            stq, perm = self._balance_ragged(stq)
            wl_len = self._balanced_worklist_len(stq)
            uniq, G = self._gather_plan(stq, wl_len)
            t1, new = self._planned(t0, (True, stq.shape[1], wl_len, G))
            fn = self._ragged_fn(wl_len, profile=True, gather_cap=G)
            res = fn(*self._arena, self._put_staged(stq),
                     self._put_staged(uniq))
            self._launched(t1, new)

            def finalize():
                r = np.asarray(res)
                out = np.empty_like(r)
                out[perm] = r
                return out[:n]

            return PendingResult(finalize, deps=(res,))
        wl_len = self._shard_worklist_len(stq)
        t1, new = self._planned(t0, (True, stq.shape[1], wl_len, None))
        fn = self._ragged_fn(wl_len, profile=True)
        res = fn(*self._arena, self._put_staged(stq))
        self._launched(t1, new)
        return PendingResult(lambda: np.asarray(res)[:n], deps=(res,))

    def _dispatch_padded_profile(self, s, t):
        n = len(s)
        npad = self._batch_pad(n)
        sp = np.zeros(npad, dtype=np.int32)
        tp = np.zeros(npad, dtype=np.int32)
        sp[:n], tp[:n] = s, t
        fn = self._padded_profile_fn()
        return fn(self.hub, self.dist, self.wlev, self.count,
                  *self._put_queries(sp, tp)), n

    def _padded_profile_fn(self):
        key = ("padded-profile", self.mode)
        if key in self._fns:
            return self._fns[key]
        P, q = self._P, self._qspec
        W = self.num_levels
        if self.mode == "replicated":
            def local(hub, dist, wlev, count, s, t):
                return profile_batch_jnp(hub, dist, wlev, count, s, t,
                                         num_levels=W)

            in_specs = (P(None, None),) * 3 + (P(None),) + (q,) * 2
        else:
            axes, rows_per = self.batch_axes, self._rows_per

            def local(hub, dist, wlev, count, s, t):
                # replicated row ids, as in the single-level fallback, but
                # ONE fused reduce-scatter per side carries (hub, dist,
                # wlev, count) together — the profile gathers a row exactly
                # once, so the collective launch is paid once too
                from ..distributed.collectives import (
                    multi_row_gather_psum_scatter)

                def side(v):
                    h, dd, ww, cc = multi_row_gather_psum_scatter(
                        (hub, dist, wlev, count[:, None]), v, axes, rows_per)
                    col = jnp.arange(h.shape[1])
                    m = col[None, :] < cc[:, 0][:, None]
                    d = jnp.where(m, jnp.minimum(dd, DEV_INF), DEV_INF)
                    w = jnp.where(m, ww, -1)
                    return h, d, w

                return _staircase_from_rows(*side(s), *side(t), W)

            in_specs = (P(self.batch_axes, None),) * 3 \
                + (P(self.batch_axes),) + (P(None),) * 2
        fn = jax.jit(jax.shard_map(local, mesh=self.mesh, in_specs=in_specs,
                                  out_specs=q, check_vma=False))
        self._fns[key] = fn
        return fn

    def _profile_segmented_fn(self):
        key = ("csr-profile", self.mode)
        if key in self._fns:
            return self._fns[key]
        P, q = self._P, self._qspec
        W = self.num_levels
        if self.mode == "replicated":
            use_pallas, interpret = self.use_pallas, self.interpret

            def local(hs, ds, ws, ht, dt, wt, stq):
                from ..kernels import ops as kops
                return kops.wcsd_profile_segmented_staged(
                    hs, ds, ws, ht, dt, wt, stq, num_levels=W,
                    interpret=interpret, use_kernel=use_pallas)

            tile = P(None, None)
            qspec = P(None, self.batch_axes)
        else:
            axes = self.batch_axes

            def local(hs, ds, ws, ht, dt, wt, stq):
                # row-sharded bucket tiles: one fused reduce-scatter per
                # side gathers (hub, dist, wlev) rows; store pads carry
                # wlev = -1 and fall below every staircase bucket
                from ..distributed.collectives import (
                    multi_row_gather_psum_scatter)
                srow, trow = stq[0], stq[1]

                def side(h, d, w, rows):
                    hg, dg, wg = multi_row_gather_psum_scatter(
                        (h, d, w), rows, axes, h.shape[0])
                    return hg, jnp.minimum(dg, DEV_INF), wg

                return _staircase_from_rows(*side(hs, ds, ws, srow),
                                            *side(ht, dt, wt, trow), W)

            tile = P(self.batch_axes, None)
            qspec = P(None, None)
        in_specs = (tile,) * 6 + (qspec,)
        fn = jax.jit(jax.shard_map(local, mesh=self.mesh, in_specs=in_specs,
                                  out_specs=q, check_vma=False))
        self._fns[key] = fn
        return fn
