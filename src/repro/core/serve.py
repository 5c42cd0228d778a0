"""WCSD serving engine: request batching over the device query engines.

Mirrors the paper's query-serving scenario (10k random queries, µs/query):
requests accumulate into fixed-size (power-of-two) batches to avoid
recompilation, are answered by one fused device call, and per-request
results are handed back. A tiny LRU memo short-circuits repeated hot
queries (social-network workloads are heavy-tailed).

Production shape:

  * pluggable engine backend — ``backend="device"`` (single-device
    `DeviceQueryEngine`), ``backend="sharded"`` (`ShardedQueryEngine` over
    a mesh), or a prebuilt engine object via ``engine=``; ``layout`` /
    ``use_pallas`` / ``interpret`` are plumbed through, so serving can
    reach the *compiled* kernels instead of being pinned to interpret mode.
  * double-buffered async flush — an auto-flush (hitting ``max_batch``)
    only *dispatches* the batch (`engine.query_async`); while the device
    executes batch k, the host keeps accepting submissions for batch k+1.
    On the default ragged dispatch the batch PLAN itself is computed on
    device (`emit_ragged_worklist`), so a flush is host-plan-free; the
    bucket-pair dispatch still plans on host (`plan_query_batch`). At most
    one batch is in flight; launching the next one (or any
    result()/flush()) drains it.
  * continuous batching — with ``max_wait_us`` set, a flush no longer
    waits for ``max_batch``: once ``min_batch`` requests are queued, the
    batch dispatches as soon as the in-flight slot is free (or its device
    work is done — `PendingResult.ready` probes without blocking), or
    once the OLDEST queued request has waited ``max_wait_us`` (checked on
    every submit and on `poll`). Below ``min_batch`` nothing fires.
    Host flush time is split into dispatch, drain wait and delivery
    (`ServeStats`), so SLO math sees launch overhead and device wait
    separately; the tracer (`tracer.start()`, core/tracing.py) adds
    per-request stamps and per-flush spans, from which
    `latency_summary` reports p50/p99 µs.
  * read-once results — `result(rid)` pops the delivered answer, so a
    long-running server's result dict stays bounded by what is queued or
    in flight instead of growing one entry per request forever. Callers
    needing an answer twice re-submit (the memo makes that free).
  * profile (staircase) queries — `submit_profile(s, t)` /
    `query_profile_many` answer EVERY constraint level of a pair in one
    label sweep (`engine.query_profile`), riding the same double-buffered
    flush; a cached profile also short-circuits any single-level submit
    of its pair (see docs/profile-queries.md).
"""
from __future__ import annotations

import collections
import dataclasses
import os
import time
from typing import Optional

import numpy as np

from . import tracing
from .query import DeviceQueryEngine, PendingResult, ShardedQueryEngine
from .resilience import (FlushRetryExhausted, RetryPolicy,
                         UnknownRequestError, WALReplayError,
                         build_fallback_ladder)
from .wc_index import (DynamicWCIndex, PackedWCIndex, WCIndex,
                       round_to_pow2)

# A drain under the watchdog probes its handle's readiness for up to
# SPIN_S with only a yield of the CPU and the GIL between probes: a
# flush's answers land within milliseconds, and a sleep between probes
# would add the OS's wake-up lag to every drain while the device sits
# idle. The yield matters too: on a TPU v5e a probe loop that keeps the
# GIL slows the runtime's own threads, so the next launch and the
# program itself take longer. A wait that outlasts SPIN_S is no ordinary
# flush (a wedged handle, a huge batch), so it then sleeps POLL_SLEEP_S
# between probes until the answers land or the deadline passes.
SPIN_S = 0.01
POLL_SLEEP_S = 1e-4


@dataclasses.dataclass
class ServeStats:
    requests: int = 0
    profile_requests: int = 0
    batches: int = 0
    memo_hits: int = 0
    # host time per flush, from the tracer's span stamps: staging, plan
    # and launch (flush.stage); blocked on device results (drain.wait);
    # handing answers out and filling the memos (drain.deliver)
    dispatch_time_s: float = 0.0
    drain_wait_s: float = 0.0
    deliver_time_s: float = 0.0
    # the part of drain_wait_s inside handle.wait() once ready() said the
    # device work was done (without a watchdog deadline, all of wait()):
    # the answers' device-to-host copy and materialisation
    readback_time_s: float = 0.0
    delivered: int = 0            # answers drains handed out (riders too)
    max_batch: int = 0
    cap_flushes: int = 0          # flushes fired by max_batch
    opportunistic_flushes: int = 0  # flushes fired by a free in-flight slot
    deadline_flushes: int = 0     # flushes fired by the max_wait_us deadline
    sync_flushes: int = 0         # flushes of flush()/result()/flush_async()
    new_programs: int = 0         # ragged flush shapes an engine ran first
    # flush watchdog (docs/resilience.md): per-cause retry counters
    timeout_retries: int = 0      # handle missed its deadline, re-dispatched
    error_retries: int = 0        # dispatch/wait raised, re-dispatched
    exhausted: int = 0            # a retry budget ran out (demote or raise)
    demotions: int = 0            # fallback-ladder steps down
    promotions: int = 0           # healthy probe windows stepping back up
    wal_appends: int = 0          # update batches logged to the WAL


class WCSDServer:
    def __init__(self, idx: WCIndex | PackedWCIndex | None = None,
                 max_batch: int = 1024, use_pallas: bool = False,
                 memo_capacity: int = 65536, layout: str = "padded",
                 undirected: bool = True, interpret: bool | None = None,
                 backend: str = "device", engine=None, mesh=None,
                 device_budget_bytes: int | None = None,
                 multi_pod: bool = False, dispatch: str = "ragged",
                 compressed: bool = False, graph=None,
                 compact_threshold: float | None = 0.25,
                 compact_kwargs: dict | None = None,
                 max_wait_us: float | None = None, min_batch: int = 1,
                 flush_timeout_ms: float | None = None,
                 max_retries: int = 3, backoff_base_ms: float = 1.0,
                 backoff_factor: float = 2.0, jitter: float = 0.5,
                 probe_interval: int = 8, retry_seed: int = 0,
                 wal_path: str | None = None, wal_fsync: bool = True,
                 engine_wrapper=None):
        # layout="csr" serves from the CSR-packed store; dispatch="ragged"
        # (default) answers each flush with ONE megakernel launch over the
        # lane-tiled arena — flush_async is plan-free on host — while
        # dispatch="bucket_pair" keeps the per-bucket-pair dispatch loop
        # (the differential oracle). compressed=True (csr + ragged only)
        # serves from the bf16/delta-coded arena (`CompressedArena`) —
        # ~2.4x the rows per device, hub ids exact, distances within the
        # documented bound.
        # A PackedWCIndex (device-resident batched builder output) is served
        # as-is under layout="csr" — no repack between build and serve.
        # undirected=False disables the symmetric (s <= t) memo
        # canonicalization for indices over directed graphs, where
        # d(s, t) != d(t, s) and the swap would alias distinct answers.
        # interpret=None resolves via kernels.ops.resolve_interpret —
        # compiled kernels on TPU, interpret emulation elsewhere.
        # graph= turns the server dynamic: idx wraps into a `DynamicWCIndex`
        # and `apply_updates` / `compact` become available; every answer is
        # stamped with the graph version it was computed against, and
        # `result_with_staleness` exposes the stamp (docs/dynamic-index.md).
        # compact_threshold triggers `compact()` when the delta grows past
        # that fraction of the base store (None disables auto-compaction).
        # max_wait_us/min_batch turn on continuous batching: once
        # min_batch requests are queued a flush fires when the in-flight
        # slot is free/finished (opportunistic) or when the oldest queued
        # request has waited max_wait_us (deadline) — max_batch remains
        # the hard cap. max_wait_us=None keeps the epoch-flush behavior.
        # flush_timeout_ms/max_retries/backoff_*/jitter arm the flush
        # watchdog: a flush that exceeds the deadline or raises is
        # cancelled and the SAME batch re-dispatched with exponential
        # backoff; an exhausted budget demotes the server one rung down
        # its fallback ladder (see `mode`), and probe_interval healthy
        # flushes re-promote it. wal_path= turns on the crash-safe update
        # WAL (every apply_updates batch is logged before the index is
        # touched; `replay_wal` warm-starts a replica). engine_wrapper=
        # wraps every engine the server builds (chaos fault injection —
        # checkpoint/fault.py `FaultyEngine`); it survives rebuilds.
        self.index = None
        self.stats = ServeStats()
        self.tracer = tracing.Tracer()    # off until tracer.start()
        self.compact_threshold = compact_threshold
        self._compact_kwargs = dict(compact_kwargs or {})
        self.retry_policy = RetryPolicy(
            flush_timeout_ms=flush_timeout_ms, max_retries=int(max_retries),
            backoff_base_ms=float(backoff_base_ms),
            backoff_factor=float(backoff_factor), jitter=float(jitter),
            probe_interval=int(probe_interval))
        self._retry_rng = np.random.default_rng(retry_seed)
        self._engine_wrapper = engine_wrapper
        self._ladder = None          # injected engines have no fallback
        self.mode_index = 0
        self._healthy = 0            # consecutive retry-free drains
        self._retry_snapshot = 0     # retry-event total at last drain
        self._retrying = False       # a drain is mid-retry: poll() backs off
        if engine is not None:
            if graph is not None:
                raise ValueError("graph= (dynamic serving) cannot be "
                                 "combined with an injected engine= — the "
                                 "server must be able to rebuild the engine "
                                 "after an update")
            self.engine = engine
        elif idx is None:
            raise ValueError("WCSDServer needs an index (idx=) or a "
                             "prebuilt engine (engine=)")
        else:
            if graph is not None and not isinstance(idx, DynamicWCIndex):
                idx = DynamicWCIndex(idx, graph)
            self.index = idx
            self._engine_config = dict(
                backend=backend, use_pallas=use_pallas, interpret=interpret,
                layout=layout, dispatch=dispatch, compressed=compressed,
                mesh=mesh, device_budget_bytes=device_budget_bytes,
                multi_pod=multi_pod)
            self._ladder = build_fallback_ladder(self._engine_config)
            self.engine = self._make_engine()
        self.wal = None
        if wal_path is not None:
            from ..checkpoint.ckpt import UpdateWAL
            self.wal = UpdateWAL(wal_path, base_version=self.graph_version,
                                 fsync=wal_fsync)
        self.max_batch = int(max_batch)
        self.max_wait_us = None if max_wait_us is None else float(max_wait_us)
        self.min_batch = max(1, int(min_batch))
        self.undirected = bool(undirected)
        self.memo: collections.OrderedDict[tuple, int] = collections.OrderedDict()
        self.memo_capacity = memo_capacity
        self.pending: list[tuple[int, int, int, int]] = []  # (rid, s, t, wl)
        self._pending_rids: set[int] = set()  # O(1) result() membership
        # pending-batch dedup: key -> position in self.pending, plus the
        # piggyback rids riding that position (mirrors _inflight_extra) —
        # a hot key submitted twice before a flush must occupy ONE device
        # slot, not two
        self._pending_pos: dict[tuple, int] = {}
        self._pending_extra: list[tuple[int, int]] = []
        self.results: dict[int, int] = {}
        # the (single) in-flight batch: (handle, rids, keys) or None
        self._inflight: Optional[tuple[PendingResult, list, list]] = None
        self._inflight_rids: set[int] = set()
        self._inflight_pos: dict[tuple, int] = {}   # key -> batch position
        self._inflight_extra: list[tuple[int, int]] = []  # (rid, position)
        # profile (staircase) requests ride the same double-buffered flush:
        # a flush dispatches one scalar batch AND one profile batch, the
        # pair forming the single in-flight slot
        self.profile_memo: collections.OrderedDict[tuple, np.ndarray] = \
            collections.OrderedDict()
        self.pending_profiles: list[tuple[int, int, int]] = []  # (rid, s, t)
        self._pending_prof_rids: set[int] = set()
        self._pending_prof_pos: dict[tuple, int] = {}
        self._pending_prof_extra: list[tuple[int, int]] = []
        self.profile_results: dict[int, np.ndarray] = {}
        self._inflight_prof: Optional[tuple[PendingResult, list, list]] = None
        self._inflight_prof_rids: set[int] = set()
        self._inflight_prof_pos: dict[tuple, int] = {}
        self._inflight_prof_extra: list[tuple[int, int]] = []
        self._next_rid = 0
        # graph version each delivered answer was computed against
        # (popped together with the answer; backs the staleness flags)
        self.result_versions: dict[int, int] = {}
        self.profile_result_versions: dict[int, int] = {}
        # fallback-ladder mode each answer was computed under ("memo" for
        # cache hits); popped with the answer, read via result_with_mode
        self.result_modes: dict[int, str] = {}
        self.profile_result_modes: dict[int, str] = {}
        # the in-flight batches' raw request tuples + dispatch closures:
        # what the watchdog re-dispatches on a retry and re-queues on a
        # terminal failure (requests are never dropped)
        self._inflight_batch: list | None = None
        self._inflight_dispatch = None
        self._inflight_prof_batch: list | None = None
        self._inflight_prof_dispatch = None
        self._pending_since: float | None = None  # oldest queued enqueue
        self._flush_seq = 0      # id of the next flush
        self._inflight_fid = tracing.PENDING  # flush holding the slot

    # ------------------------------------------------------------- dynamic
    def _make_engine(self):
        cfg = (self._ladder[self.mode_index][1]
               if self._ladder is not None else self._engine_config)
        eng = self._build_engine(cfg)
        eng.tracer = self.tracer
        eng.serve_stats = self.stats
        if self._engine_wrapper is not None:
            eng = self._engine_wrapper(eng)
        return eng

    def _build_engine(self, cfg):
        if cfg["backend"] == "device":
            return DeviceQueryEngine(
                self.index, use_pallas=cfg["use_pallas"],
                interpret=cfg["interpret"], layout=cfg["layout"],
                dispatch=cfg["dispatch"], compressed=cfg["compressed"])
        if cfg["backend"] == "sharded":
            return ShardedQueryEngine(
                self.index, mesh=cfg["mesh"], use_pallas=cfg["use_pallas"],
                interpret=cfg["interpret"], layout=cfg["layout"],
                device_budget_bytes=cfg["device_budget_bytes"],
                multi_pod=cfg["multi_pod"], dispatch=cfg["dispatch"],
                compressed=cfg["compressed"])
        raise ValueError(f"unknown backend: {cfg['backend']!r} "
                         "(expected 'device' or 'sharded')")

    @property
    def graph_version(self) -> int:
        return int(getattr(self.index, "graph_version", 0))

    # ---------------------------------------------------------- resilience
    @property
    def mode(self) -> str:
        """The fallback-ladder rung currently serving ("primary" when
        healthy; "injected" for engine= servers, which have no ladder).
        Every delivered answer is stamped with the mode that produced it
        (`result_with_mode`)."""
        if self._ladder is None:
            return "injected"
        return self._ladder[self.mode_index][0]

    def _demote(self) -> bool:
        """Step one rung down the fallback ladder (rebuilding the engine
        in place) after an exhausted retry budget. False at the bottom —
        nothing left to fall back to. The memos survive: every rung
        serves the same index, so answers are mode-independent."""
        if self._ladder is None or self.mode_index >= len(self._ladder) - 1:
            return False
        self.mode_index += 1
        self.stats.demotions += 1
        self._healthy = 0
        self.engine = self._make_engine()
        return True

    def _stamp_deadline(self, handle) -> None:
        p = self.retry_policy
        if p.flush_timeout_ms is not None:
            try:
                handle.deadline = (time.monotonic()
                                   + p.flush_timeout_ms / 1e3)
            except AttributeError:
                pass  # foreign handle type without the attribute

    def _dispatch_with_retry(self, dispatch):
        """Run a zero-arg dispatch closure under the watchdog: a raise is
        retried with exponential backoff + jitter up to ``max_retries``;
        an exhausted budget demotes one rung (resetting the budget) or —
        at the bottom of the ladder — re-raises as `FlushRetryExhausted`
        with the pending queue intact. The closure reads ``self.engine``
        at call time, so a retry after a demotion uses the new engine."""
        p = self.retry_policy
        attempt = 0
        while True:
            try:
                handle = dispatch()
            except Exception as err:
                attempt += 1
                if attempt > p.max_retries:
                    self.stats.exhausted += 1
                    if self._demote():
                        attempt = 0
                    else:
                        raise FlushRetryExhausted(
                            f"dispatch failed after {p.max_retries} "
                            f"retries at mode {self.mode!r} (bottom of "
                            "the fallback ladder); the requests are "
                            "still queued") from err
                else:
                    self.stats.error_retries += 1
                time.sleep(p.backoff_s(max(attempt, 1), self._retry_rng))
                continue
            self._stamp_deadline(handle)
            return handle

    def _await_handle(self, handle, redispatch):
        """`handle.wait()` under the watchdog. A handle past its deadline
        that still is not ready is abandoned (device work is not
        interruptible — its result is simply never read) and the SAME
        batch re-dispatched via ``redispatch``; a raising wait() retries
        the same way. Exhaustion demotes one rung and resets the budget;
        at the bottom it raises `FlushRetryExhausted` (the caller
        re-queues the batch — nothing is dropped). With a deadline armed,
        readiness is probed with a yield between probes (see SPIN_S) and
        the deadline checked on every probe; the time inside a successful
        `wait()` is counted in ``readback_time_s``."""
        p = self.retry_policy
        attempt = 0
        while True:
            timed_out, err = False, None
            deadline = getattr(handle, "deadline", None)
            if deadline is not None:
                spin_until = time.monotonic() + SPIN_S
                while not handle.ready():
                    now = time.monotonic()
                    if now > deadline:
                        timed_out = True
                        break
                    if now > spin_until:
                        time.sleep(POLL_SLEEP_S)
                    else:
                        os.sched_yield()
            if not timed_out:
                t0 = tracing.now()
                try:
                    out = handle.wait()
                except Exception as e:
                    err = e
                else:
                    self.stats.readback_time_s += (tracing.now() - t0) * 1e-9
                    return out
            attempt += 1
            if attempt > p.max_retries:
                self.stats.exhausted += 1
                if self._demote():
                    attempt = 0
                else:
                    raise FlushRetryExhausted(
                        f"flush failed after {p.max_retries} retries at "
                        f"mode {self.mode!r} (bottom of the fallback "
                        "ladder); the batch has been re-queued") from err
            elif timed_out:
                self.stats.timeout_retries += 1
            else:
                self.stats.error_retries += 1
            time.sleep(p.backoff_s(max(attempt, 1), self._retry_rng))
            handle = redispatch()

    def apply_updates(self, inserts=(), deletes=()) -> dict:
        """Mutate the served graph and fold the label corrections into the
        delta store (`DynamicWCIndex.apply_updates`). In-flight and pending
        requests are flushed FIRST: their answers stay valid for the graph
        version they were stamped with, and read back as stale. The scalar
        and profile memos are dropped (their entries answer the old graph)
        and the engine is rebuilt over the delta-extended store. Crossing
        ``compact_threshold`` triggers `compact` before returning.

        With a WAL attached (``wal_path=``), the mutation batch is logged
        — checksummed and fsynced — BEFORE the index is touched: a crash
        anywhere after the append loses nothing, because a replica
        warm-starting from the last checkpoint replays the tail
        (`replay_wal`) and converges to the pre-crash graph version."""
        if not isinstance(self.index, DynamicWCIndex):
            raise ValueError("apply_updates requires a dynamic server — "
                             "construct WCSDServer(idx, graph=g, ...)")
        self.flush()
        inserts = [(int(u), int(v), float(q)) for u, v, q in inserts]
        deletes = [(int(u), int(v)) for u, v in deletes]
        if self.wal is not None:
            self.wal.append(inserts, deletes,
                            graph_version=self.graph_version + 1)
            self.stats.wal_appends += 1
        stats = self.index.apply_updates(inserts=inserts, deletes=deletes)
        self.memo.clear()
        self.profile_memo.clear()
        self.engine = self._make_engine()
        stats["compacted"] = False
        if (self.compact_threshold is not None
                and self.index.delta_ratio() >= self.compact_threshold):
            self.compact()
            stats["compacted"] = True
        return stats

    def compact(self, **build_kwargs) -> dict:
        """Fold the delta into a fresh immutable base store (fused Pareto
        pass + arena re-pack; byte-identical to a from-scratch build on the
        current graph) and rebuild the engine over it. Answers are unchanged
        by construction, so the memos survive compaction."""
        if not isinstance(self.index, DynamicWCIndex):
            raise ValueError("compact requires a dynamic server — "
                             "construct WCSDServer(idx, graph=g, ...)")
        self.flush()
        kw = dict(self._compact_kwargs)
        kw.update(build_kwargs)
        stats = self.index.compact(**kw)
        self.engine = self._make_engine()
        if self.wal is not None:
            # the compacted base now embodies every logged record: restart
            # the log at the current version (atomic header rewrite)
            self.wal.truncate(self.graph_version)
        return stats

    def replay_wal(self) -> int:
        """Warm start: re-apply the WAL tail past the server's current
        graph version, in order, converging to the pre-crash state.
        Returns the number of records applied. Raises `WALReplayError`
        when the log does not reach back to this server's version (it was
        compacted past the checkpoint this replica loaded). Replayed
        batches are NOT re-appended to the log — they are already in it."""
        if self.wal is None:
            raise ValueError("replay_wal requires a WAL-backed server — "
                             "construct WCSDServer(..., wal_path=...)")
        if not isinstance(self.index, DynamicWCIndex):
            raise ValueError("replay_wal requires a dynamic server — "
                             "construct WCSDServer(idx, graph=g, ...)")
        n = 0
        for rec in self.wal.replay(self.graph_version):
            if rec["graph_version"] != self.graph_version + 1:
                raise WALReplayError(
                    f"WAL record jumps to graph version "
                    f"{rec['graph_version']} but the server is at "
                    f"{self.graph_version}")
            self.flush()
            self.index.apply_updates(
                inserts=[(int(u), int(v), float(q))
                         for u, v, q in rec["inserts"]],
                deletes=[(int(u), int(v)) for u, v in rec["deletes"]])
            n += 1
        if n:
            self.memo.clear()
            self.profile_memo.clear()
            self.engine = self._make_engine()
        return n

    def _memo_key(self, s: int, t: int, w_level: int) -> tuple:
        if self.undirected and s > t:
            return (t, s, w_level)
        return (s, t, w_level)

    def _profile_key(self, s: int, t: int) -> tuple:
        # per-level distances are symmetric exactly when single-level ones
        # are, so the profile key follows the same directed gate
        if self.undirected and s > t:
            return (t, s)
        return (s, t)

    # ------------------------------------------------------------- requests
    def submit(self, s: int, t: int, w_level: int) -> int:
        """Queue one request; returns a request id."""
        tr = self.tracer
        t_enq = tracing.now() if tr.on else 0
        rid = self._next_rid
        self._next_rid += 1
        key = self._memo_key(s, t, w_level)
        pkey = self._profile_key(s, t)
        self.stats.requests += 1
        fid, rides = tracing.PENDING, False
        if key in self.memo:
            self.memo.move_to_end(key)
            self.results[rid] = self.memo[key]
            self.result_versions[rid] = self.graph_version
            self.result_modes[rid] = "memo"
            self.stats.memo_hits += 1
            fid = tracing.MEMO
        elif (pkey in self.profile_memo
              and 0 <= w_level <= getattr(self.engine, "num_levels", -1)):
            # a cached profile answers EVERY level of its pair: read the
            # staircase instead of queueing device work, and promote the
            # level into the scalar memo so exact repeats stay O(1)
            self.profile_memo.move_to_end(pkey)
            self.results[rid] = int(self.profile_memo[pkey][w_level])
            self.result_versions[rid] = self.graph_version
            self.result_modes[rid] = "memo"
            self._memo_put(key, self.results[rid])
            self.stats.memo_hits += 1
            fid = tracing.MEMO
        elif key in self._inflight_pos:
            # the answer is already being computed in the in-flight batch:
            # piggyback on it instead of re-queueing the hot key (counted
            # as a memo hit — no extra device work happens)
            self._inflight_extra.append((rid, self._inflight_pos[key]))
            self._inflight_rids.add(rid)
            self.stats.memo_hits += 1
            fid, rides = self._inflight_fid, True
        elif key in self._pending_pos:
            # already queued but not yet dispatched: ride the queued
            # request's batch slot instead of occupying a second one
            self._pending_extra.append((rid, self._pending_pos[key]))
            self._pending_rids.add(rid)
            self.stats.memo_hits += 1
            rides = True
        else:
            if not self.pending and not self.pending_profiles:
                self._pending_since = time.perf_counter()
            self._pending_pos[key] = len(self.pending)
            self.pending.append((rid, s, t, w_level))
            self._pending_rids.add(rid)
            if tr.on:
                tr.enqueue(rid, t_enq, fid)
            self._maybe_flush()
            return rid
        if tr.on:
            tr.enqueue(rid, t_enq, fid, rides)
        return rid

    def submit_profile(self, s: int, t: int) -> int:
        """Queue one profile request — the full ``dist(s, t, w)`` staircase
        for every level 0..num_levels, answered by ONE label sweep (see
        `DeviceQueryEngine.query_profile`). Returns a request id for
        `profile_result`."""
        tr = self.tracer
        t_enq = tracing.now() if tr.on else 0
        rid = self._next_rid
        self._next_rid += 1
        key = self._profile_key(s, t)
        self.stats.profile_requests += 1
        fid, rides = tracing.PENDING, False
        if key in self.profile_memo:
            self.profile_memo.move_to_end(key)
            self.profile_results[rid] = self.profile_memo[key].copy()
            self.profile_result_versions[rid] = self.graph_version
            self.profile_result_modes[rid] = "memo"
            self.stats.memo_hits += 1
            fid = tracing.MEMO
        elif key in self._inflight_prof_pos:
            self._inflight_prof_extra.append(
                (rid, self._inflight_prof_pos[key]))
            self._inflight_prof_rids.add(rid)
            self.stats.memo_hits += 1
            fid, rides = self._inflight_fid, True
        elif key in self._pending_prof_pos:
            self._pending_prof_extra.append(
                (rid, self._pending_prof_pos[key]))
            self._pending_prof_rids.add(rid)
            self.stats.memo_hits += 1
            rides = True
        else:
            if not self.pending and not self.pending_profiles:
                self._pending_since = time.perf_counter()
            self._pending_prof_pos[key] = len(self.pending_profiles)
            self.pending_profiles.append((rid, s, t))
            self._pending_prof_rids.add(rid)
            if tr.on:
                tr.enqueue(rid, t_enq, fid)
            self._maybe_flush()
            return rid
        if tr.on:
            tr.enqueue(rid, t_enq, fid, rides)
        return rid

    def _slot_done(self) -> bool:
        """True iff a batch is in flight AND its device work has finished
        (a drain would not block)."""
        if self._inflight is None and self._inflight_prof is None:
            return False
        return ((self._inflight is None or self._inflight[0].ready())
                and (self._inflight_prof is None
                     or self._inflight_prof[0].ready()))

    def _maybe_flush(self) -> None:
        """Continuous-batching admission: fire a flush when the hard cap
        is hit, or — with ``max_wait_us`` enabled and at least
        ``min_batch`` queued — when the in-flight slot is free/finished
        (opportunistic) or the oldest queued request has aged past the
        deadline. No-op while a retry is in progress: dispatching a new
        batch mid-retry would race the half-retried slot."""
        if self._retrying:
            return
        npend = len(self.pending) + len(self.pending_profiles)
        if npend >= self.max_batch:
            # async: dispatch only — the device chews on this batch
            # while the host accepts and plans the next one
            self.stats.cap_flushes += 1
            self.flush_async("cap")
            return
        if self.max_wait_us is None or npend < self.min_batch:
            return
        if self._inflight is None and self._inflight_prof is None \
                or self._slot_done():
            self.stats.opportunistic_flushes += 1
            self.flush_async("opportunistic")
        elif (self._pending_since is not None
              and (time.perf_counter() - self._pending_since) * 1e6
              >= self.max_wait_us):
            self.stats.deadline_flushes += 1
            self.flush_async("deadline")

    def poll(self) -> None:
        """Deadline tick for continuous batching: harvest the in-flight
        batch if its device work is done (delivering its results without
        blocking) and re-check the flush triggers. Callers with gaps
        between submissions call this to bound queueing delay; `submit`
        runs the same checks on every enqueue.

        Re-entrancy guard: while the watchdog is mid-retry (a drain
        re-dispatched a timed-out or raising batch and is waiting on the
        replacement handle), the in-flight slot is half-retried state —
        harvesting it, or dispatching a new batch over it, would deliver
        from the abandoned handle or race two batches on one engine.
        `poll` during a retry is a no-op; the retrying drain delivers."""
        if self._retrying:
            return
        if self._slot_done():
            self._drain()
        self._maybe_flush()

    def latency_summary(self) -> dict:
        """p50/p99 (µs) of enqueue→deliver latency over every request the
        tracer recorded and saw delivered (memo hits included — they
        deliver at enqueue). With nothing delivered, or the tracer never
        on, the percentiles are zeros with ``n == count == 0`` — never an
        exception."""
        req = tracing.request_times(self.tracer.snapshot())
        if not len(req["rid"]):
            return {"count": 0, "n": 0, "p50_us": 0.0, "p99_us": 0.0}
        arr = (req["deliver_ns"] - req["enqueue_ns"]) * 1e-3
        return {"count": int(arr.size), "n": int(arr.size),
                "p50_us": float(np.percentile(arr, 50)),
                "p99_us": float(np.percentile(arr, 99))}

    def _memo_put(self, key: tuple, value: int) -> None:
        self.memo[key] = value
        if len(self.memo) > self.memo_capacity:
            self.memo.popitem(last=False)

    def flush_async(self, cause: str = "sync") -> None:
        """Dispatch the pending batch without waiting for its results.

        Double-buffered: at most one batch is in flight, so dispatching
        batch k+1 first drains batch k (by then typically long finished).
        A flush dispatches the pending scalar batch AND the pending profile
        batch (either may be empty); together they form the in-flight slot.

        Failure semantics (docs/resilience.md): the pending queue is
        cleared only AFTER its dispatch returns, and the dispatch itself
        runs under the flush watchdog — an engine raise (sharded gather
        OOM, a poisoned compile cache, an injected chaos fault, ...) is
        retried with backoff, then absorbed by a fallback-ladder demotion;
        only at the bottom of the ladder does `FlushRetryExhausted`
        propagate, with every queued request still pending — a later
        flush retries the same batch and `result(rid)` still
        blocks-and-answers instead of failing forever.

        ``cause`` names the trigger for the tracer: "cap", "opportunistic"
        and "deadline" come from the admission checks, "sync" (the
        default) from `flush`, `result` and direct calls.
        """
        if not self.pending and not self.pending_profiles:
            return
        self._drain()
        t0 = tracing.now()
        if cause == "sync":
            self.stats.sync_flushes += 1
        fid = self._inflight_fid = self._flush_seq
        self._flush_seq += 1
        tr = self.tracer
        if tr.on:
            tr.open_flush(fid, cause, len(self.pending)
                          + len(self.pending_profiles))
        # pad to the next power of two (bounded recompiles); the csr engine
        # pads each planned sub-batch itself, and the sharded engine pads to
        # its own device multiple, so padding here would only add dummy
        # queries that the kernels compute and discard
        pad_here = (getattr(self.engine, "layout", "padded") == "padded"
                    and not isinstance(self.engine, ShardedQueryEngine))
        if self.pending:
            batch = self.pending
            n = len(batch)
            padded = round_to_pow2(n) if pad_here else n
            s = np.zeros(padded, dtype=np.int32)
            t = np.zeros(padded, dtype=np.int32)
            wl = np.zeros(padded, dtype=np.int32)
            s[:n] = [b[1] for b in batch]
            t[:n] = [b[2] for b in batch]
            wl[:n] = [b[3] for b in batch]

            def dispatch(s=s, t=t, wl=wl):
                # reads self.engine at call time, so a retry after a
                # fallback-ladder demotion dispatches to the new engine
                qa = getattr(self.engine, "query_async", None)
                if qa is not None:
                    return qa(s, t, wl)
                # engine exposes only a blocking query (tests stub this)
                res = self.engine.query(s, t, wl)
                return PendingResult(lambda: res)

            # dispatch BEFORE the queue is cleared (see docstring)
            handle = self._dispatch_with_retry(dispatch)
            if tr.on:
                tr.carry([b[0] for b in batch]
                         + [r for r, _ in self._pending_extra], fid)
            keys = [self._memo_key(b[1], b[2], b[3]) for b in batch]
            self._inflight = (handle, [b[0] for b in batch], keys)
            self._inflight_batch = batch
            self._inflight_dispatch = dispatch
            # pending piggybacks ride over: positions are batch positions
            self._inflight_rids = ({b[0] for b in batch}
                                   | {r for r, _ in self._pending_extra})
            self._inflight_pos = {k: i for i, k in enumerate(keys)}
            self._inflight_extra = list(self._pending_extra)
            self.pending = []
            self._pending_rids = set()
            self._pending_pos = {}
            self._pending_extra = []
            self.stats.max_batch = max(self.stats.max_batch, n)
        if self.pending_profiles:
            batch = self.pending_profiles
            n = len(batch)
            padded = round_to_pow2(n) if pad_here else n
            s = np.zeros(padded, dtype=np.int32)
            t = np.zeros(padded, dtype=np.int32)
            s[:n] = [b[1] for b in batch]
            t[:n] = [b[2] for b in batch]

            def prof_dispatch(s=s, t=t):
                qa = getattr(self.engine, "query_profile_async", None)
                if qa is not None:
                    return qa(s, t)
                res = self.engine.query_profile(s, t)
                return PendingResult(lambda: res)

            handle = self._dispatch_with_retry(prof_dispatch)
            if tr.on:
                tr.carry([b[0] for b in batch]
                         + [r for r, _ in self._pending_prof_extra], fid)
            keys = [self._profile_key(b[1], b[2]) for b in batch]
            self._inflight_prof = (handle, [b[0] for b in batch], keys)
            self._inflight_prof_batch = batch
            self._inflight_prof_dispatch = prof_dispatch
            self._inflight_prof_rids = ({b[0] for b in batch}
                                        | {r for r, _ in
                                           self._pending_prof_extra})
            self._inflight_prof_pos = {k: i for i, k in enumerate(keys)}
            self._inflight_prof_extra = list(self._pending_prof_extra)
            self.pending_profiles = []
            self._pending_prof_rids = set()
            self._pending_prof_pos = {}
            self._pending_prof_extra = []
            self.stats.max_batch = max(self.stats.max_batch, n)
        self._pending_since = None
        self.stats.batches += 1
        t1 = tracing.now()
        self.stats.dispatch_time_s += (t1 - t0) * 1e-9
        if tr.on:
            tr.span(tracing.STAGE, t0, t1, fid)

    def _requeue_scalar(self, batch, extra) -> None:
        """Put a terminally-failed in-flight batch back at the FRONT of
        the pending queue (nothing is dropped): existing pending
        positions and piggyback slots shift by the batch length; the
        failed batch's own piggybacks keep their 0-based positions."""
        n = len(batch)
        self.pending = list(batch) + self.pending
        shifted = {k: p + n for k, p in self._pending_pos.items()}
        for i, b in enumerate(batch):
            # on a duplicate key the queued copy wins (it already carries
            # piggybacks pointing at its shifted position)
            shifted.setdefault(self._memo_key(b[1], b[2], b[3]), i)
        self._pending_pos = shifted
        self._pending_extra = ([(r, p) for r, p in extra]
                               + [(r, p + n) for r, p in self._pending_extra])
        self._pending_rids |= {b[0] for b in batch} | {r for r, _ in extra}
        if self._pending_since is None:
            self._pending_since = time.perf_counter()

    def _requeue_profile(self, batch, extra) -> None:
        n = len(batch)
        self.pending_profiles = list(batch) + self.pending_profiles
        shifted = {k: p + n for k, p in self._pending_prof_pos.items()}
        for i, b in enumerate(batch):
            shifted.setdefault(self._profile_key(b[1], b[2]), i)
        self._pending_prof_pos = shifted
        self._pending_prof_extra = (
            [(r, p) for r, p in extra]
            + [(r, p + n) for r, p in self._pending_prof_extra])
        self._pending_prof_rids |= ({b[0] for b in batch}
                                    | {r for r, _ in extra})
        if self._pending_since is None:
            self._pending_since = time.perf_counter()

    def _drain(self) -> None:
        """Materialize the in-flight batch into results + memos.

        Runs under the flush watchdog: a timed-out or raising handle is
        re-dispatched with backoff (`_await_handle`); a terminal failure
        re-queues the batch and propagates. The ``_retrying`` guard makes
        the drain non-reentrant — `poll()` (including one issued
        re-entrantly by a retried engine) must not harvest the
        half-retried slot."""
        if self._retrying:
            return
        if self._inflight is None and self._inflight_prof is None:
            return
        ver = self.graph_version
        fid = self._inflight_fid
        self.tracer.cur = fid     # a re-dispatch's engine spans
        self._retrying = True
        try:
            if self._inflight is not None:
                handle, rids, keys = self._inflight
                extra = self._inflight_extra
                batch = self._inflight_batch
                dispatch = self._inflight_dispatch
                self._inflight = None
                self._inflight_rids = set()
                self._inflight_pos = {}
                self._inflight_extra = []
                self._inflight_batch = None
                self._inflight_dispatch = None
                t0 = tracing.now()
                try:
                    out = self._await_handle(
                        handle,
                        lambda: self._dispatch_with_retry(dispatch))
                except Exception:
                    self._requeue_scalar(batch, extra)
                    raise
                t1 = tracing.now()
                out = out[:len(rids)]
                mode = self.mode
                for rid, key, d in zip(rids, keys, out):
                    self.results[rid] = int(d)
                    self.result_versions[rid] = ver
                    self.result_modes[rid] = mode
                    self._memo_put(key, int(d))
                for rid, pos in extra:  # duplicates submitted in flight
                    self.results[rid] = int(out[pos])
                    self.result_versions[rid] = ver
                    self.result_modes[rid] = mode
                self._drained(fid, t0, t1, len(rids) + len(extra))
            if self._inflight_prof is not None:
                handle, rids, keys = self._inflight_prof
                extra = self._inflight_prof_extra
                batch = self._inflight_prof_batch
                dispatch = self._inflight_prof_dispatch
                self._inflight_prof = None
                self._inflight_prof_rids = set()
                self._inflight_prof_pos = {}
                self._inflight_prof_extra = []
                self._inflight_prof_batch = None
                self._inflight_prof_dispatch = None
                t0 = tracing.now()
                try:
                    out = self._await_handle(
                        handle,
                        lambda: self._dispatch_with_retry(dispatch))
                except Exception:
                    self._requeue_profile(batch, extra)
                    raise
                t1 = tracing.now()
                out = np.asarray(out)[:len(rids)]
                mode = self.mode
                for rid, key, prof in zip(rids, keys, out):
                    # np.array COPIES: the memo must own its staircase,
                    # not a row view pinning the whole flushed batch
                    # buffer (and aliasing what profile_result hands out
                    # as caller-owned)
                    arr = np.array(prof, dtype=np.int32)
                    self.profile_results[rid] = arr.copy()
                    self.profile_result_versions[rid] = ver
                    self.profile_result_modes[rid] = mode
                    self.profile_memo[key] = arr
                    if len(self.profile_memo) > self.memo_capacity:
                        self.profile_memo.popitem(last=False)
                for rid, pos in extra:
                    self.profile_results[rid] = np.array(out[pos],
                                                         dtype=np.int32)
                    self.profile_result_versions[rid] = ver
                    self.profile_result_modes[rid] = mode
                self._drained(fid, t0, t1, len(rids) + len(extra))
        finally:
            self._retrying = False
        # health accounting: a drain that completed with no new retry
        # events is a healthy flush; probe_interval of them in a row
        # re-promotes a degraded server one rung up the ladder
        events = (self.stats.timeout_retries + self.stats.error_retries
                  + self.stats.exhausted)
        if events == self._retry_snapshot:
            self._healthy += 1
        else:
            self._healthy = 0
        self._retry_snapshot = events
        if (self._ladder is not None and self.mode_index > 0
                and self._healthy >= self.retry_policy.probe_interval):
            self.mode_index -= 1
            self.stats.promotions += 1
            self._healthy = 0
            self.engine = self._make_engine()

    def _drained(self, fid: int, t0: int, t1: int, n: int) -> None:
        """Account one drained batch: waited from t0 to t1, then handed
        ``n`` answers out until now."""
        t2 = tracing.now()
        st = self.stats
        st.drain_wait_s += (t1 - t0) * 1e-9
        st.deliver_time_s += (t2 - t1) * 1e-9
        st.delivered += n
        tr = self.tracer
        if tr.on:
            tr.span(tracing.WAIT, t0, t1, fid)
            tr.span(tracing.DELIVER, t1, t2, fid)

    def flush(self) -> None:
        """Synchronous flush: dispatch anything pending and drain."""
        self.flush_async()
        self._drain()

    def result(self, rid: int) -> int:
        """Deliver (and evict) the answer for ``rid``.

        Read-once contract: a delivered rid is popped from the result dict,
        so per-request state cannot accumulate across a server's lifetime.
        An unknown — or already-delivered — rid raises the typed
        `UnknownRequestError` without disturbing the pending queue."""
        return self._pop_result(rid)[0]

    def _pop_result(self, rid: int):
        if rid not in self.results:
            if rid in self._inflight_rids:
                self._drain()
            elif rid in self._pending_rids:
                self.flush()
        if rid in self.results:
            return (self.results.pop(rid),
                    self.result_versions.pop(rid, self.graph_version),
                    self.result_modes.pop(rid, self.mode))
        raise UnknownRequestError(rid)

    def result_with_staleness(self, rid: int):
        """`result`, plus whether the answer predates the served graph:
        ``(value, stale)`` where ``stale`` is True iff the answer was
        computed against an earlier graph version than the server now
        holds (it was in flight or pending when `apply_updates` ran).
        Unknown rids raise `UnknownRequestError`."""
        value, ver, _mode = self._pop_result(rid)
        return value, ver < self.graph_version

    def result_with_mode(self, rid: int):
        """`result`, plus the fallback-ladder mode that computed the
        answer: ``(value, mode)`` where mode is a ladder rung name
        ("primary", "uncompressed", ..., "oracle") or "memo" for a cache
        hit. A degraded server keeps answering — correctly, from a
        simpler engine — and this is how callers see it happened."""
        value, _ver, mode = self._pop_result(rid)
        return value, mode

    def result_full(self, rid: int):
        """``(value, graph_version, mode)`` — the answer plus everything
        stamped on it (the chaos harness checks each answer against the
        oracle for exactly the graph version that produced it)."""
        return self._pop_result(rid)

    def profile_result(self, rid: int) -> np.ndarray:
        """Deliver (and evict) the ``[num_levels + 1]`` staircase for a
        `submit_profile` rid — the same read-once contract (and typed
        `UnknownRequestError`) as `result`. The delivered array is the
        caller's to keep (the memo holds its own copy)."""
        return self._pop_profile_result(rid)[0]

    def _pop_profile_result(self, rid: int):
        if rid not in self.profile_results:
            if rid in self._inflight_prof_rids:
                self._drain()
            elif rid in self._pending_prof_rids:
                self.flush()
        if rid in self.profile_results:
            return (self.profile_results.pop(rid),
                    self.profile_result_versions.pop(rid,
                                                     self.graph_version),
                    self.profile_result_modes.pop(rid, self.mode))
        raise UnknownRequestError(rid)

    def profile_result_with_staleness(self, rid: int):
        """`profile_result` + the staleness flag (see
        `result_with_staleness`)."""
        value, ver, _mode = self._pop_profile_result(rid)
        return value, ver < self.graph_version

    def profile_result_with_mode(self, rid: int):
        """`profile_result` + the producing mode (see
        `result_with_mode`)."""
        value, _ver, mode = self._pop_profile_result(rid)
        return value, mode

    def profile_result_full(self, rid: int):
        """``(staircase, graph_version, mode)`` (see `result_full`)."""
        return self._pop_profile_result(rid)

    # convenience: synchronous bulk APIs
    def query_many(self, s, t, w_level) -> np.ndarray:
        rids = [self.submit(int(a), int(b), int(c))
                for a, b, c in zip(s, t, w_level)]
        self.flush()
        return np.array([self.result(r) for r in rids], dtype=np.int32)

    def query_profile_many(self, s, t) -> np.ndarray:
        """[n, num_levels + 1] staircases for n (s, t) pairs."""
        rids = [self.submit_profile(int(a), int(b)) for a, b in zip(s, t)]
        self.flush()
        out = [self.profile_result(r) for r in rids]
        W1 = self.engine.num_levels + 1
        if not out:
            return np.zeros((0, W1), dtype=np.int32)
        return np.stack(out).astype(np.int32)

    def query_profile(self, s: int, t: int) -> np.ndarray:
        """Synchronous single-pair staircase."""
        return self.query_profile_many([s], [t])[0]
