"""Public jit'd wrappers for the Pallas kernels: shape padding, feasibility
masking, and dispatch (kernel vs jnp fallback). Everything here is safe to
call from traced code."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import cin_fuse as _cin
from . import frontier as _frontier
from . import ref as _ref
from . import wcsd_query as _wq

DEV_INF = 1 << 29  # python int: safe to close over in pallas kernels
INF_DIST = 1 << 30


def _ceil_to(x: int, m: int) -> int:
    return int(-(-x // m) * m)


def resolve_interpret(interpret: bool | None) -> bool:
    """THE resolution point for the Pallas ``interpret`` flag.

    Every engine takes ``interpret=None`` by default and resolves it here,
    so ``use_pallas=True`` engines reach the COMPILED kernels whenever the
    backend can lower them — interpret mode is for explicit requests and
    backends without Mosaic support, not a silent production default.

    Only the TPU backend resolves to compiled: every kernel in this
    package is TPU Pallas (`pltpu.PrefetchScalarGridSpec` scalar
    prefetch), which neither CPU nor GPU can lower — those backends
    emulate.

    Resolution table (locked by tests/test_ragged.py):

        interpret arg | backend      | resolved
        --------------+--------------+---------
        True          | any          | True
        False         | any          | False
        None          | tpu          | False  (compiled Mosaic kernels)
        None          | cpu/gpu/...  | True   (no Mosaic: emulate)
    """
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def wcsd_query(hub, dist, wlev, count, s, t, w_level, *,
               interpret: bool = True, use_kernel: bool = True):
    """Batched WCSD queries against padded device labels.

    hub/dist/wlev: [V, L] int32, count: [V], queries s/t/w_level: [B].
    Returns [B] int32 distances (INF_DIST when no feasible path)."""
    B = s.shape[0]
    L = hub.shape[1]
    col = jnp.arange(L)

    def side(v):
        m = (col[None, :] < count[v, None]) & (wlev[v] >= w_level[:, None])
        d = jnp.where(m, jnp.minimum(dist[v], DEV_INF), DEV_INF)
        return hub[v], d

    hs, ds = side(s)
    ht, dt = side(t)
    if use_kernel:
        Bp = _ceil_to(max(B, 1), 8)
        Lp = _ceil_to(L, 128)
        pad_b, pad_l = Bp - B, Lp - L
        # hub pad: -1 on s side, -2 on t side -> never equal
        hs = jnp.pad(hs, ((0, pad_b), (0, pad_l)), constant_values=-1)
        ht = jnp.pad(ht, ((0, pad_b), (0, pad_l)), constant_values=-2)
        ds = jnp.pad(ds, ((0, pad_b), (0, pad_l)), constant_values=DEV_INF)
        dt = jnp.pad(dt, ((0, pad_b), (0, pad_l)), constant_values=DEV_INF)
        best = _wq.wcsd_query_gathered(hs, ds, ht, dt,
                                       interpret=interpret)[:B]
    else:
        best = _ref.wcsd_query_gathered_ref(hs, ds, ht, dt)
    return jnp.where(best >= DEV_INF, INF_DIST, best).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def wcsd_query_segmented(hub_s, dist_s, wlev_s, hub_t, dist_t, wlev_t,
                         srow, trow, w_level, *, interpret: bool = True,
                         use_kernel: bool = True):
    """One bucket-pair sub-batch of the segmented CSR query path.

    hub_s/dist_s/wlev_s: [Ns, Ws] s-side bucket tiles, hub_t/...: [Nt, Wt]
    t-side tiles (Ws, Wt multiples of 128; pad contract hub = -1,
    wlev = -1). srow/trow: [B] row ids into the tiles, w_level: [B].
    Returns [B] int32 distances (INF_DIST when no feasible path)."""
    if use_kernel:
        best = _wq.wcsd_query_segmented(hub_s, dist_s, wlev_s,
                                        hub_t, dist_t, wlev_t,
                                        srow, trow, w_level,
                                        interpret=interpret)
    else:
        best = _ref.wcsd_query_segmented_ref(hub_s, dist_s, wlev_s,
                                             hub_t, dist_t, wlev_t,
                                             srow, trow, w_level)
    return jnp.where(best >= DEV_INF, INF_DIST, best).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def wcsd_query_segmented_staged(hub_s, dist_s, wlev_s, hub_t, dist_t, wlev_t,
                                stq, *, interpret: bool = True,
                                use_kernel: bool = True):
    """`wcsd_query_segmented` fed by ONE fused staging array: ``stq`` is
    [3, B] int32 carrying (srow, trow, w_level) stacked, so a planned
    sub-batch pays a single H2D transfer instead of three — the unpack
    happens on device, inside this jit."""
    return wcsd_query_segmented(hub_s, dist_s, wlev_s, hub_t, dist_t, wlev_t,
                                stq[0], stq[1], stq[2], interpret=interpret,
                                use_kernel=use_kernel)


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def wcsd_query_ragged(hub, dist, wlev, tile_lo, tile_hi,
                      qidx, stile, ttile, wq, *,
                      interpret: bool = True, use_kernel: bool = True):
    """One ragged sub-batch — which is the WHOLE batch: every bucket mix in
    one flush over the lane-tiled arena (see `kernels.wcsd_query.
    wcsd_query_ragged` for the worklist contract and the SMEM split).
    Returns [Q] int32 distances (INF_DIST when no feasible path)."""
    if use_kernel:
        best = _wq.wcsd_query_ragged(hub, dist, wlev, tile_lo, tile_hi,
                                     qidx, stile, ttile, wq,
                                     interpret=interpret)
    else:
        best = _ref.wcsd_query_ragged_ref(hub, dist, wlev, qidx, stile,
                                          ttile, wq)
    return jnp.where(best >= DEV_INF, INF_DIST, best).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_rows", "num_levels",
                                             "interpret", "use_kernel"))
def wcsd_profile_ragged(hub, dist, wlev, tile_lo, tile_hi,
                        qidx, stile, ttile, *, num_rows: int,
                        num_levels: int, interpret: bool = True,
                        use_kernel: bool = True):
    """Ragged PROFILE batch: same worklist contract as `wcsd_query_ragged`,
    every constraint level answered from the one sweep. The kernel (or its
    jnp oracle) emits per-pair-level bucket minima; the suffix min-scan
    applied here turns them into staircases. Returns
    [num_rows, num_levels + 1] int32 (INF_DIST where infeasible)."""
    if use_kernel:
        bucket = _wq.wcsd_profile_ragged(hub, dist, wlev, tile_lo, tile_hi,
                                         qidx, stile, ttile,
                                         num_rows=num_rows,
                                         num_levels=num_levels,
                                         interpret=interpret)
    else:
        bucket = _ref.wcsd_profile_ragged_ref(hub, dist, wlev, qidx, stile,
                                              ttile, num_rows, num_levels)
    prof = jax.lax.cummin(bucket, axis=1, reverse=True)
    return jnp.where(prof >= DEV_INF, INF_DIST, prof).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def wcsd_query_ragged_compressed(hub_delta, dist, wlev, tile_lo, tile_hi,
                                 qidx, stile, ttile, wq, *,
                                 interpret: bool = True,
                                 use_kernel: bool = True):
    """`wcsd_query_ragged` over the COMPRESSED arena (CompressedArena
    fields; decode happens in-kernel / in the oracle). Same worklist and
    output contract; callers must route overflowed stores to the
    uncompressed path."""
    if use_kernel:
        best = _wq.wcsd_query_ragged(
            hub_delta, dist, wlev, tile_lo, tile_hi,
            qidx, stile, ttile, wq, interpret=interpret)
    else:
        best = _ref.wcsd_query_ragged_compressed_ref(
            hub_delta, dist, wlev, tile_lo, qidx, stile, ttile, wq)
    return jnp.where(best >= DEV_INF, INF_DIST, best).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_rows", "num_levels",
                                             "interpret", "use_kernel"))
def wcsd_profile_ragged_compressed(hub_delta, dist, wlev, tile_lo, tile_hi,
                                   qidx, stile, ttile, *,
                                   num_rows: int, num_levels: int,
                                   interpret: bool = True,
                                   use_kernel: bool = True):
    """`wcsd_profile_ragged` over the COMPRESSED arena."""
    if use_kernel:
        bucket = _wq.wcsd_profile_ragged(
            hub_delta, dist, wlev, tile_lo, tile_hi,
            qidx, stile, ttile, num_rows=num_rows,
            num_levels=num_levels, interpret=interpret)
    else:
        bucket = _ref.wcsd_profile_ragged_compressed_ref(
            hub_delta, dist, wlev, tile_lo, qidx, stile, ttile,
            num_rows, num_levels)
    prof = jax.lax.cummin(bucket, axis=1, reverse=True)
    return jnp.where(prof >= DEV_INF, INF_DIST, prof).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_levels", "interpret",
                                             "use_kernel"))
def wcsd_profile_segmented(hub_s, dist_s, wlev_s, hub_t, dist_t, wlev_t,
                           srow, trow, *, num_levels: int,
                           interpret: bool = True, use_kernel: bool = True):
    """One bucket-pair sub-batch of the one-pass PROFILE query path.

    Same tile/row-id contract as `wcsd_query_segmented`, minus the
    per-query level: both label rows are gathered once and every
    constraint level is answered from that single sweep. The kernel (or
    its jnp oracle) emits per-pair-level bucket minima; the suffix
    min-scan over the level axis applied here turns them into the
    staircase. Returns [B, num_levels + 1] int32 distances —
    ``out[b, w] == wcsd_query_segmented(..., w)[b]`` pointwise, with
    INF_DIST where no feasible path exists."""
    if use_kernel:
        bucket = _wq.wcsd_profile_segmented(hub_s, dist_s, wlev_s,
                                            hub_t, dist_t, wlev_t,
                                            srow, trow,
                                            num_levels=num_levels,
                                            interpret=interpret)
    else:
        bucket = _ref.wcsd_profile_segmented_ref(hub_s, dist_s, wlev_s,
                                                 hub_t, dist_t, wlev_t,
                                                 srow, trow, num_levels)
    prof = jax.lax.cummin(bucket, axis=1, reverse=True)
    return jnp.where(prof >= DEV_INF, INF_DIST, prof).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_levels", "interpret",
                                             "use_kernel"))
def wcsd_profile_segmented_staged(hub_s, dist_s, wlev_s,
                                  hub_t, dist_t, wlev_t, stq, *,
                                  num_levels: int, interpret: bool = True,
                                  use_kernel: bool = True):
    """`wcsd_profile_segmented` fed by one fused [2, B] (srow, trow)
    staging array — single H2D per planned sub-batch, unpacked in-jit."""
    return wcsd_profile_segmented(hub_s, dist_s, wlev_s,
                                  hub_t, dist_t, wlev_t, stq[0], stq[1],
                                  num_levels=num_levels, interpret=interpret,
                                  use_kernel=use_kernel)


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def frontier_relax(nbr_pad, lvl_pad, Fw, R, *, interpret: bool = True,
                   use_kernel: bool = True):
    """One constrained-relaxation round over a padded adjacency.

    nbr_pad/lvl_pad: [V, D] (pad: nbr=-1, lvl=-1); Fw/R: [V] int32.
    Returns (newF, newR), both [V]."""
    fw_nbr = Fw[jnp.clip(nbr_pad, 0, Fw.shape[0] - 1)]
    fw_nbr = jnp.where(nbr_pad >= 0, fw_nbr, -1)
    if not use_kernel:
        return _ref.frontier_relax_gathered_ref(fw_nbr, lvl_pad, R)
    V, D = fw_nbr.shape
    bV = 256 if V % 256 == 0 else (64 if V % 64 == 0 else 8)
    Vp = _ceil_to(V, bV)
    if Vp != V:
        fw_nbr = jnp.pad(fw_nbr, ((0, Vp - V), (0, 0)), constant_values=-1)
        lvl_pad = jnp.pad(lvl_pad, ((0, Vp - V), (0, 0)), constant_values=-1)
        R = jnp.pad(R, (0, Vp - V), constant_values=jnp.int32(1 << 20))
    newf, newr = _frontier.frontier_relax_gathered(
        fw_nbr, lvl_pad, R, block_v=bV, interpret=interpret)
    return newf[:V], newr[:V]


def _pick_block_v(V: int) -> int:
    return 256 if V % 256 == 0 else (64 if V % 64 == 0 else 8)


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel",
                                             "do_prune"))
def wc_prune_emit(F, T, hub, dist, wlev, d, *, do_prune: bool = True,
                  interpret: bool = True, use_kernel: bool = True):
    """Fused partial-index prune + emission for a batch of roots.

    F: [B, V] frontier levels (-1 inactive); T: [B, V, W+1] per-root hub
    tables (indexed by hub rank); hub/dist/wlev: [V, cap] padded partial
    index; d: scalar current round. Returns emit_w [B, V] (-1 = no emit).
    With do_prune=False (round 0) the whole active frontier emits."""
    if not do_prune:
        return F
    if not use_kernel:
        return _ref.wc_prune_emit_batched_ref(F, T, hub, dist, wlev, d)
    B, V = F.shape
    bV = _pick_block_v(V)
    Vp = _ceil_to(V, bV)
    if Vp != V:
        F = jnp.pad(F, ((0, 0), (0, Vp - V)), constant_values=-1)
        hub = jnp.pad(hub, ((0, Vp - V), (0, 0)), constant_values=-1)
        dist = jnp.pad(dist, ((0, Vp - V), (0, 0)), constant_values=INF_DIST)
        wlev = jnp.pad(wlev, ((0, Vp - V), (0, 0)), constant_values=-1)
    emit = _frontier.wc_prune_emit_batched(
        F, T, hub, dist, wlev, jnp.asarray(d, jnp.int32).reshape(1),
        block_v=bV, interpret=interpret)
    return emit[:, :V]


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def wc_relax_batched(emit_w, nbr_pad, lvl_pad, rank, root_ranks, R, *,
                     interpret: bool = True, use_kernel: bool = True):
    """One batched constrained-relaxation round.

    emit_w/R: [B, V]; nbr_pad/lvl_pad: [V, D] (pad: nbr = -1, lvl = -1);
    rank: [V] vertex -> rank; root_ranks: [B]. Returns (newF, newR)."""
    B, V = emit_w.shape
    rank2 = rank[None, :]
    if not use_kernel:
        return _ref.wc_relax_batched_ref(emit_w, nbr_pad, lvl_pad, rank2,
                                         root_ranks, R)
    bV = _pick_block_v(V)
    Vp = _ceil_to(V, bV)
    if Vp != V:
        emit_w = jnp.pad(emit_w, ((0, 0), (0, Vp - V)), constant_values=-1)
        nbr_pad = jnp.pad(nbr_pad, ((0, Vp - V), (0, 0)), constant_values=-1)
        lvl_pad = jnp.pad(lvl_pad, ((0, Vp - V), (0, 0)), constant_values=-1)
        rank2 = jnp.pad(rank2, ((0, 0), (0, Vp - V)), constant_values=-1)
        R = jnp.pad(R, ((0, 0), (0, Vp - V)),
                    constant_values=jnp.int32(1 << 20))
    newf, newr = _frontier.wc_relax_batched(
        emit_w, nbr_pad, lvl_pad, rank2, root_ranks, R,
        block_v=bV, interpret=interpret)
    return newf[:, :V], newr[:, :V]


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel",
                                             "block_b"))
def cin_layer(x1, x0, w, *, interpret: bool = True, use_kernel: bool = True,
              block_b: int = 8):
    """Fused CIN layer; pads batch to the block size."""
    if not use_kernel:
        return _ref.cin_layer_ref(x1, x0, w)
    B = x1.shape[0]
    Bp = _ceil_to(max(B, 1), block_b)
    if Bp != B:
        x1 = jnp.pad(x1, ((0, Bp - B), (0, 0), (0, 0)))
        x0 = jnp.pad(x0, ((0, Bp - B), (0, 0), (0, 0)))
    out = _cin.cin_layer(x1, x0, w, block_b=block_b, interpret=interpret)
    return out[:B]
