"""Pallas TPU kernel for batched WCSD 2-hop label intersection (the paper's
Algorithm 5 hot path, restructured for the MXU/VPU).

CPU Alg. 5 is a pointer sort-merge — hostile to SIMD. On TPU we compute, per
query, a masked outer join over the two padded label rows:

    best = min_{i,j} [ hub_s[i] == hub_t[j] ] * (d_s[i] + d_t[j])
           subject to w_s[i] >= w, w_t[j] >= w

The [B, L, L] compare volume never touches HBM: the kernel tiles the t-side
label axis, keeps the s-side row resident in VMEM, and accumulates the
min-plus reduction in a [bB, 1] output block. XLA on the same computation
materializes the [B, L, L] intermediate (see benchmarks/bench_kernels.py).

Feasibility masking (w >= threshold, entry in-bounds) is pre-applied by
ops.py by overwriting infeasible distances with DEV_INF, so the kernel body
is a pure equality-gated min-plus — one VPU compare + add + min per cell.

Layout contract (from core.query.DeviceQueryEngine / WCIndex):
  label rows are hub-sorted, L padded to a multiple of 128 with hub = -1,
  dist = DEV_INF; pad cells can never win the min because DEV_INF + DEV_INF
  < int32 max yet > any real distance sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEV_INF = 1 << 29  # python int: safe to close over in pallas kernels


def _query_kernel(hs_ref, ds_ref, ht_ref, dt_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, DEV_INF)

    hs = hs_ref[...]            # [bB, L]   (s-side: full label row)
    ds = ds_ref[...]
    ht = ht_ref[...]            # [bB, bLt] (t-side tile)
    dt = dt_ref[...]
    eq = hs[:, :, None] == ht[:, None, :]            # [bB, L, bLt]
    dsum = ds[:, :, None] + dt[:, None, :]
    best = jnp.where(eq, dsum, DEV_INF).min(axis=(1, 2))
    out_ref[...] = jnp.minimum(out_ref[...], best[:, None])


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_lt", "interpret"))
def wcsd_query_gathered(hs, ds, ht, dt, *, block_b: int = 8,
                        block_lt: int = 128, interpret: bool = True):
    """Masked-distance form: [B, L] gathered label rows -> [B] best sum.

    ds/dt must already hold DEV_INF at infeasible entries.
    B % block_b == 0, L % block_lt == 0 (ops.py pads).
    """
    B, L = hs.shape
    grid = (B // block_b, L // block_lt)
    out = pl.pallas_call(
        _query_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, L), lambda i, j: (i, 0)),    # hs
            pl.BlockSpec((block_b, L), lambda i, j: (i, 0)),    # ds
            pl.BlockSpec((block_b, block_lt), lambda i, j: (i, j)),  # ht
            pl.BlockSpec((block_b, block_lt), lambda i, j: (i, j)),  # dt
        ],
        out_specs=pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        interpret=interpret,
    )(hs, ds, ht, dt)
    return out[:, 0]


def _fit_block(block_lt: int, Wt: int) -> int:
    """Largest t-tile block width <= ``block_lt`` that DIVIDES ``Wt`` —
    the grid is ``Wt // block_lt`` steps, so a non-divisor block would
    silently drop Wt's tail columns (non-128-multiple widths are reachable
    through the engines' ``lane`` knob: lane=48 gives Wt = 48, 96, 192...).
    Halving always terminates at a divisor (worst case 1)."""
    if Wt <= block_lt:
        return Wt
    while Wt % block_lt:
        block_lt //= 2
    return block_lt


# ------------------------------------------------------------ output layout
#
# Every kernel below answers one scalar (or one [W + 1] bucket row) per
# query, accumulated over many grid steps. The TPU lowering only accepts
# blocks whose last two dims are (8, 128)-divisible or whole, so a
# per-query (1, 1) output block cannot lower. Instead the whole output
# stays resident in VMEM for the launch as lane-dense (8, 128) int32
# tiles — query q lives at flat position q of tile q // 1024 — and is
# written back to HBM once, after the last grid step. Shape
# [levels, ceil(rows / 1024), 8, 128]; ``levels`` is 1 for single-level
# queries and W + 1 for profiles.
_OUT_TILE = 8 * 128


def _out_shape(levels: int, rows: int):
    return jax.ShapeDtypeStruct((levels, -(-rows // _OUT_TILE), 8, 128),
                                jnp.int32)


def _unpack_out(out, rows: int):
    """[levels, R, 8, 128] kernel output -> [rows, levels] int32."""
    return out.reshape(out.shape[0], -1)[:, :rows].T


def _min_into(out_ref, q, best):
    """``out[q] = min(out[q], best)`` for a (1, 1) ``best``: one masked
    select over the (8, 128) tile that holds query ``q``."""
    r = q // _OUT_TILE
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    hit = (sub == (q // 128) % 8) & (lane == q % 128)
    cur = out_ref[r]
    out_ref[r] = jnp.where(hit, jnp.minimum(cur, best), cur)


def _join_into(out_ref, q, s_cells, t_cells, wq):
    """The equality-gated min-plus of one (s, t) tile pair, folded into
    query ``q``'s output. ``*_cells`` are (hub, dist, wlev) [1, n] int32
    rows with dist already clamped to DEV_INF; pads carry hub -1 and
    wlev -1. ``wq`` is the query level (single-level: cells below it are
    masked) or None (profile: each meet's sum lands in the bucket of its
    pair level ``min(wlev_s, wlev_t)``; the staircase scan runs in ops).
    The s-side row is transposed to a column so the [n_s, n_t] compare
    is a plain broadcast."""
    hs, ds, ws = s_cells
    ht, dt, wt = t_cells
    if wq is not None:
        ds = jnp.where(ws >= wq, ds, DEV_INF)
        dt = jnp.where(wt >= wq, dt, DEV_INF)
    dsum = jnp.where(hs.T == ht, ds.T + dt, DEV_INF)
    if wq is not None:
        _min_into(out_ref.at[0], q, jnp.min(dsum, keepdims=True))
        return
    mw = jnp.minimum(ws.T, wt)
    for lev in range(out_ref.shape[0]):   # static: W + 1 is tiny
        best = jnp.min(jnp.where(mw == lev, dsum, DEV_INF), keepdims=True)
        _min_into(out_ref.at[lev], q, best)


# --------------------------------------------------------------- segmented
def _segmented_kernel(levels):
    def kernel(*refs):
        if levels is None:
            srow_ref, trow_ref, wq_ref, *refs = refs
        else:
            srow_ref, trow_ref, *refs = refs
        hs_ref, ds_ref, ws_ref, ht_ref, dt_ref, wt_ref, out_ref, \
            s_buf, t_buf, sems = refs
        i, a, b = pl.program_id(0), pl.program_id(1), pl.program_id(2)

        @pl.when((i == 0) & (a == 0) & (b == 0))
        def _init():
            out_ref[...] = jnp.full(out_ref.shape, DEV_INF, jnp.int32)

        # chunk a of row srow[i] is row srow[i] * num_programs(1) + a of
        # the chunk-major store view (see `_segmented_call`)
        srow = srow_ref[i] * pl.num_programs(1) + a
        trow = trow_ref[i] * pl.num_programs(2) + b
        srcs = (hs_ref, ds_ref, ws_ref, ht_ref, dt_ref, wt_ref)
        rows = (srow,) * 3 + (trow,) * 3
        dsts = [s_buf.at[j] for j in range(3)] + [t_buf.at[j] for j in range(3)]
        copies = [pltpu.make_async_copy(src.at[pl.ds(row, 1)], dst, sems.at[c])
                  for c, (src, row, dst) in enumerate(zip(srcs, rows, dsts))]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

        def cells(buf):
            return buf[0], jnp.minimum(buf[1], DEV_INF), buf[2]

        _join_into(out_ref, i, cells(s_buf), cells(t_buf),
                   None if levels is not None else wq_ref[i])
    return kernel


def _segmented_call(hub_s, dist_s, wlev_s, hub_t, dist_t, wlev_t, scalars,
                    levels, block_lt, interpret):
    """Shared launch of the two bucket-pair kernels. The bucket tiles are
    viewed chunk-major ([N, W] -> [N * W / c, c], a row-major reshape), so
    each grid step (query i, s-chunk a, t-chunk b) DMAs one whole row of
    each view straight out of HBM — the query's row ids arrive by scalar
    prefetch, so the gather IS the DMA — and folds the chunk pair's join
    into the resident output."""
    B = scalars[0].shape[0]
    Ws, Wt = hub_s.shape[1], hub_t.shape[1]
    cs, ct = _fit_block(block_lt, Ws), _fit_block(block_lt, Wt)
    side_s = [a.reshape(-1, cs) for a in (hub_s, dist_s, wlev_s)]
    side_t = [a.reshape(-1, ct) for a in (hub_t, dist_t, wlev_t)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, Ws // cs, Wt // ct),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 6,
        out_specs=pl.BlockSpec(_out_shape(levels or 1, B).shape,
                               lambda *_: (0, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((3, 1, cs), jnp.int32),
                        pltpu.VMEM((3, 1, ct), jnp.int32),
                        pltpu.SemaphoreType.DMA((6,))],
    )
    out = pl.pallas_call(
        _segmented_kernel(levels),
        grid_spec=grid_spec,
        out_shape=_out_shape(levels or 1, B),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
    )(*scalars, *side_s, *side_t)
    return _unpack_out(out, B)


@functools.partial(jax.jit, static_argnames=("block_lt", "interpret"))
def wcsd_query_segmented(hub_s, dist_s, wlev_s, hub_t, dist_t, wlev_t,
                         srow, trow, w_level, *, block_lt: int = 128,
                         interpret: bool = True):
    """Bucket-pair query path: gathers CSR label rows in-kernel.

    Unlike `wcsd_query_gathered`, whose caller materializes [B, L] gathered
    + masked copies in HBM, this kernel reads label rows straight out of the
    bucket-tiled store: the query's row ids arrive as scalar-prefetch
    arguments (`PrefetchScalarGridSpec`) and each grid step DMAs one
    ``block_lt``-wide chunk of row ``srow[i]`` and one of row ``trow[i]``.
    Feasibility masking (wlev >= w) happens in-kernel, which lets both
    query sides share one store — per query the HBM traffic is
    3·(Ws + Wt) int32 per chunk pair instead of 4·2·L after host-side
    gather/mask.

    hub_s/dist_s/wlev_s: [Ns, Ws] s-side bucket tiles (pad: hub -1,
    wlev -1); hub_t/...: [Nt, Wt] t-side tiles. srow/trow/w_level: [B]
    int32. Ws and Wt may differ (that is the point: a (128, 128) bucket
    pair does 1/64th the compares of a 1024-padded dense row pair).
    Returns [B] int32 best sums (>= DEV_INF means infeasible).
    """
    return _segmented_call(hub_s, dist_s, wlev_s, hub_t, dist_t, wlev_t,
                           (srow, trow, w_level), None, block_lt,
                           interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=("num_levels", "block_lt",
                                             "interpret"))
def wcsd_profile_segmented(hub_s, dist_s, wlev_s, hub_t, dist_t, wlev_t,
                           srow, trow, *, num_levels: int,
                           block_lt: int = 128, interpret: bool = True):
    """One-pass profile queries: per-(vertex-pair) wlev-bucket minima.

    Same store layout and scalar-prefetch gather as `wcsd_query_segmented`,
    but no per-query level: each query reads its two label rows ONCE and
    bins every hub meet's distance sum by its pair level
    ``min(wlev_s, wlev_t)``. Returns [B, num_levels + 1] int32 bucket
    minima — ``out[b, l]`` is the best sum among pairs whose pair level
    (the tightest constraint they satisfy) is exactly ``l``
    (>= DEV_INF: none). The full
    staircase ``dist(s, t, w)`` for every ``w`` is the suffix min-scan over
    the level axis (`ops.wcsd_profile_segmented` applies it), making the
    L-level workload one label sweep instead of L.
    """
    return _segmented_call(hub_s, dist_s, wlev_s, hub_t, dist_t, wlev_t,
                           (srow, trow), int(num_levels) + 1, block_lt,
                           interpret)


# ------------------------------------------------------------------ ragged
#
# The ragged kernels fetch their arena tiles MANUALLY: the arena stays in
# HBM (`memory_space=ANY`) and each work item's six tiles are DMA'd into a
# quad-buffered VMEM scratch ring (`_RAGGED_NBUF` slots x six buffers, one
# DMA semaphore per copy). The automatic BlockSpec pipeline only
# double-buffers and serializes its prefetch one grid step ahead; with the
# explicit ring the copy for worklist entry k + 4 is issued the moment slot
# k % 4 frees, so on skewed stores the O(lane^2) join of entry k overlaps
# the HBM latency of the next THREE entries.
#
# Scalar prefetch carries one int32 per work item and array, and nothing
# sized by the arena or the batch: the tile-span early-out and the query
# level are gathered per work item on the XLA side (``ctl``), so the
# kernel's SMEM footprint is a fixed number of words per work item. A
# worklist longer than the SMEM budget admits runs as several launches of
# equal length whose outputs are min-combined (exact: min is associative).
_RAGGED_NBUF = 4

# Scalar memory of one v5e TensorCore is 1 MiB. Worklist scalars may use
# half of it; the rest is headroom for the kernel's own scalars.
_SMEM_BYTES = 1 << 20
_PREFETCH_BUDGET_WORDS = _SMEM_BYTES // 4 // 2

# The compressed arena's narrow dtypes are tiled 8 rows deep in HBM, so a
# DMA can only start at a row multiple of 8: the kernel fetches the
# aligned 8-tile group and picks its tile's row after widening.
_ROW_GROUP = 8


def pad_group_rows(hub, dist, wlev):
    """Pad a compressed arena trio's tile rows to a multiple of
    `_ROW_GROUP` (pad rows are never named by a worklist), so the aligned
    group DMA stays in bounds. A no-op on aligned arenas; the engines pad
    once at load so no flush pays the copy."""
    T = hub.shape[0]
    if T % _ROW_GROUP == 0:
        return hub, dist, wlev
    rows = ((0, -T % _ROW_GROUP), (0, 0))
    return tuple(jnp.pad(a, rows) for a in (hub, dist, wlev))


def ragged_launch_capacity(compressed: bool = False) -> int:
    """Work items one ragged launch may hold: its scalar-prefetch words
    (four per item, six on the compressed arena) within the SMEM budget."""
    return _PREFETCH_BUDGET_WORDS // (6 if compressed else 4)


def ragged_launches(worklist_len: int, compressed: bool = False
                    ) -> tuple[int, int]:
    """(launches, work items per launch) for a ragged worklist: ONE launch
    whenever it fits `ragged_launch_capacity`, else the fewest equal
    launches that each fit (the last is padded with no-op items)."""
    n = max(1, -(-worklist_len // ragged_launch_capacity(compressed)))
    return n, -(-worklist_len // n)


def _fetch_ring(stile_ref, ttile_ref, srcs, bufs, sems, group):
    """DMA-descriptor factory for one worklist entry: six async copies
    (s-side and t-side hub/dist/wlev tiles, or their aligned ``group``
    of tiles) into ring slot ``slot``.

    Start/wait calls must balance per (slot, copy) semaphore: every entry
    k is started exactly once (warmup for k < NBUF, else the prefetch at
    step k - NBUF) and waited exactly once (step k)."""
    def copies(slot, entry):
        s = stile_ref[entry]
        t = ttile_ref[entry]
        if group > 1:
            s = pl.multiple_of(s // group * group, group)
            t = pl.multiple_of(t // group * group, group)
        idxs = (s, s, s, t, t, t)
        return [pltpu.make_async_copy(src.at[pl.ds(ix, group)],
                                      buf.at[slot], sems.at[slot, j])
                for j, (src, ix, buf) in enumerate(zip(srcs, idxs, bufs))]
    return copies


def _fetch_wait(k, WL, copies, nbuf=_RAGGED_NBUF):
    """Warmup (step 0 issues the first ``nbuf`` entries), then block on
    this entry's slot. Returns the slot index owning entry ``k``'s
    tiles."""
    @pl.when(k == 0)
    def _warmup():
        for i in range(min(nbuf, WL)):
            for c in copies(i, i):
                c.start()

    slot = jax.lax.rem(k, nbuf)
    for c in copies(slot, k):
        c.wait()
    return slot


def _fetch_next(k, WL, slot, copies, nbuf=_RAGGED_NBUF):
    """Reuse the slot just consumed for entry ``k + nbuf`` (clamped read:
    the guard keeps the copy from running, the clamp keeps the scalar
    load in bounds)."""
    if WL > nbuf:
        @pl.when(k + nbuf < WL)
        def _prefetch():
            nxt = jnp.minimum(k + nbuf, WL - 1)
            for c in copies(slot, nxt):
                c.start()


def _decode_cells(hd, d, w, lo, row):
    """In-register decode of one compressed arena tile (CompressedArena,
    docs/index-format.md §6) from its fetched 8-tile group: widen, pick
    row ``row``, then rebuild int16 hub deltas against the tile's lo rank
    (the sign is the pad flag, so -1 sentinels survive), clamp bfloat16
    distances at DEV_INF — the +inf pad encoding saturates there, so no
    isfinite test is needed — and round back to int32 (+0.5 then
    truncate; exact for every in-range integer the float format holds)."""
    sub = jax.lax.broadcasted_iota(jnp.int32, hd.shape, 0)

    def pick(x):
        return jnp.sum(jnp.where(sub == row, x, 0), axis=0, keepdims=True)

    hd = pick(hd.astype(jnp.int32))
    hub = jnp.where(hd >= 0, lo + hd, -1)
    df = jnp.minimum(pick(d.astype(jnp.float32)), float(DEV_INF))
    return hub, (df + 0.5).astype(jnp.int32), pick(w.astype(jnp.int32))


def _ragged_kernel(WL, nbuf, single_level, compressed):
    def kernel(*refs):
        qidx_ref, stile_ref, ttile_ref, ctl_ref = refs[:4]
        # compressed: the two tiles' hub bases ride along as scalars
        slo_ref, tlo_ref = refs[4:6] if compressed else (None, None)
        hub_ref, dist_ref, wlev_ref, out_ref, *bufs, sems = \
            refs[6 if compressed else 4:]
        k = pl.program_id(0)

        @pl.when(k == 0)
        def _init():
            out_ref[...] = jnp.full(out_ref.shape, DEV_INF, jnp.int32)

        copies = _fetch_ring(stile_ref, ttile_ref,
                             (hub_ref, dist_ref, wlev_ref) * 2, bufs, sems,
                             _ROW_GROUP if compressed else 1)
        slot = _fetch_wait(k, WL, copies, nbuf)
        ctl = ctl_ref[k]

        # Thm.-3 rows are hub-sorted, so each arena tile covers one
        # hub-rank interval; ctl bit 0 says the two tiles' intervals
        # intersect. Disjoint intervals cannot meet -> skip the O(lane^2)
        # join (the DMA already happened, the saving is compute — and on
        # skewed stores most cross-tile pairs of a long x long query are
        # disjoint).
        @pl.when((ctl & 1) == 1)
        def _join():
            def cells(b0, lo_ref, tile_ref):
                h, d, w = (b[slot] for b in bufs[b0:b0 + 3])
                if compressed:
                    return _decode_cells(h, d, w, lo_ref[k],
                                         tile_ref[k] % _ROW_GROUP)
                return h, jnp.minimum(d, DEV_INF), w

            _join_into(out_ref, qidx_ref[k],
                       cells(0, slo_ref, stile_ref),
                       cells(3, tlo_ref, ttile_ref),
                       ctl >> 1 if single_level else None)

        _fetch_next(k, WL, slot, copies, nbuf)
    return kernel


def _ragged_call(hub, dist, wlev, tile_lo, tile_hi, qidx, stile, ttile,
                 wq, num_rows, levels, interpret, nbuf):
    """Shared launch of the four ragged kernels (plain / compressed x
    single-level / profile). Builds the per-work-item scalars, splits the
    worklist into SMEM-sized launches and min-combines their outputs.
    Returns [num_rows, levels] int32 raw minima (>= DEV_INF: none)."""
    compressed = hub.dtype != jnp.int32
    meet = ((tile_lo[stile] <= tile_hi[ttile])
            & (tile_lo[ttile] <= tile_hi[stile])).astype(jnp.int32)
    ctl = meet if wq is None else (wq[qidx] << 1) | meet
    scalars = [qidx, stile, ttile, ctl]
    if compressed:
        scalars += [tile_lo[stile], tile_lo[ttile]]
        hub, dist, wlev = pad_group_rows(hub, dist, wlev)
    n, L = ragged_launches(qidx.shape[0], compressed)
    if n * L != qidx.shape[0]:
        # no-op pads: ctl 0 never joins, row 0 / tile 0 are in bounds
        scalars = [jnp.pad(a, (0, n * L - a.shape[0])) for a in scalars]
    group = _ROW_GROUP if compressed else 1
    lane = hub.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(L,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec(_out_shape(levels, num_rows).shape,
                               lambda *_: (0, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((nbuf, group, lane), a.dtype)
                        for a in (hub, dist, wlev) * 2]
        + [pltpu.SemaphoreType.DMA((nbuf, 6))],
    )
    kernel = _ragged_kernel(L, nbuf, wq is not None, compressed)
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    outs = [pl.pallas_call(kernel, grid_spec=grid_spec,
                           out_shape=_out_shape(levels, num_rows),
                           compiler_params=params, interpret=interpret)(
                *(a[i * L:(i + 1) * L] for a in scalars), hub, dist, wlev)
            for i in range(n)]
    return _unpack_out(functools.reduce(jnp.minimum, outs), num_rows)


@functools.partial(jax.jit, static_argnames=("interpret", "nbuf"))
def wcsd_query_ragged(hub, dist, wlev, tile_lo, tile_hi,
                      qidx, stile, ttile, wq, *,
                      interpret: bool = True, nbuf: int = _RAGGED_NBUF):
    """Ragged query path over the lane-tiled label arena.

    Collapses the whole bucket-pair dispatch loop into ONE `pallas_call`
    (several only when the worklist outgrows SMEM, see
    `ragged_launches`): the grid is a flat worklist of
    ``(query, s_tile, t_tile)`` work items (one per tile pair of a
    query's two label rows — see `core.query.emit_ragged_worklist`). The
    arena stays HBM-resident and each entry's tiles are fetched through
    the quad-buffered DMA ring (see the section comment), so a batch
    mixing every bucket length runs with zero wasted lanes and the tile
    DMA of entry k + 4 overlapping the join of entry k.

    hub/dist/wlev: [T, lane] int32 arena tiles (pad contract hub -1,
    wlev -1) — or the `CompressedArena` trio (int16 hub deltas, bfloat16
    distances, int8 levels), decoded in-kernel; tile_lo/tile_hi: [T]
    per-tile hub-rank spans (Thm.-3 early-out; tile_lo is also the hub
    base of a compressed tile); qidx/stile/ttile: [WL] int32 worklist;
    wq: [Q] per-output-row query levels (worklist pads must point at a
    trash row whose level is infeasible). Returns [Q] int32 best sums
    (>= DEV_INF means infeasible).

    ``nbuf`` sizes the DMA ring (default quad-buffered); ``nbuf=1`` is
    the no-overlap baseline the serving bench's ``dma_overlap_speedup``
    row compares against.
    """
    return _ragged_call(hub, dist, wlev, tile_lo, tile_hi, qidx, stile,
                        ttile, wq, wq.shape[0], 1, interpret, nbuf)[:, 0]


@functools.partial(jax.jit, static_argnames=("num_rows", "num_levels",
                                             "interpret", "nbuf"))
def wcsd_profile_ragged(hub, dist, wlev, tile_lo, tile_hi,
                        qidx, stile, ttile, *, num_rows: int,
                        num_levels: int, interpret: bool = True,
                        nbuf: int = _RAGGED_NBUF):
    """Ragged PROFILE path: same arena/worklist contract (plain or
    compressed trio, quad-buffered tile fetch, SMEM split) as
    `wcsd_query_ragged`, no per-query level — each work item bins its hub
    meets' distance sums by pair level ``min(wlev_s, wlev_t)`` into the
    query's [num_levels + 1] bucket row (the staircase is the suffix
    min-scan, applied in ops). Returns [num_rows, num_levels + 1] int32
    bucket minima; worklist pads must point at trash row num_rows - 1."""
    return _ragged_call(hub, dist, wlev, tile_lo, tile_hi, qidx, stile,
                        ttile, None, num_rows, int(num_levels) + 1,
                        interpret, nbuf)
