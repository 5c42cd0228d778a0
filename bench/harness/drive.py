"""Drive a `WCSDServer` through its public calls: `submit` (or
`submit_profile` for a profile request), `poll`, and `result_with_mode`
(`profile_result_with_mode`) once `results` (`profile_results`) holds
the answer.

The harness is the client. It stamps each request's due time, the time
it called `submit`, and the time it found the answer delivered, and it
times every call into the server, so the host time spent inside the
server's front end is known per request. One thread does all of it, as a
single-threaded client process would.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from .traffic import POINT, PROFILE

pc = time.perf_counter


@dataclasses.dataclass
class Requests:
    """One stream of requests and what happened to each."""
    s: np.ndarray
    t: np.ndarray
    w: np.ndarray
    due: np.ndarray        # perf_counter seconds the request was due
    submit: np.ndarray     # perf_counter seconds of the submit call
    deliver: np.ndarray    # perf_counter seconds the answer was found
    answer: np.ndarray     # a point request's distance
    mode: np.ndarray       # 0 primary, 1 memo, 2 any other rung
    n: int = 0             # requests submitted
    kind: np.ndarray | None = None     # POINT or PROFILE, int8
    profile: np.ndarray | None = None  # [n, W + 1] staircases; None
                                       # when no request is a profile

    @classmethod
    def empty(cls, s, t, w, kind=None, num_levels: int = 0):
        """Requests (s, t, w) not yet due; ``kind`` (None: every request
        is a point) and the graph's ``num_levels`` size the staircases."""
        n = len(s)
        if kind is None:
            kind = np.full(n, POINT, np.int8)
        profile = (np.zeros((n, num_levels + 1), np.int64)
                   if np.any(kind == PROFILE) else None)
        return cls(s=s, t=t, w=w, due=np.full(n, np.nan),
                   submit=np.full(n, np.nan), deliver=np.full(n, np.nan),
                   answer=np.zeros(n, np.int64), mode=np.zeros(n, np.int8),
                   kind=kind, profile=profile)


class HostClock:
    """Seconds spent inside each kind of server call, plus the calls'
    spans while ``span_from`` <= start (the traced part of the window)."""

    def __init__(self):
        self.seconds = {"submit": 0.0, "poll": 0.0, "result": 0.0}
        self.spans: list[tuple[str, int, int]] = []
        self.span_from = float("inf")

    def add(self, kind: str, t0: float, t1: float) -> None:
        self.seconds[kind] += t1 - t0
        if t0 >= self.span_from:
            a, b = int(t0 * 1e9), int(t1 * 1e9)
            spans = self.spans
            # back-to-back calls of one kind (an idle poll loop) make one span
            if spans and spans[-1][0] == kind and a - spans[-1][2] < 5000:
                spans[-1] = (kind, spans[-1][1], b)
            else:
                spans.append((kind, a, b))


_MODES = {"primary": 0, "memo": 1}


def _collect(results, pop, out, req, rid_at, t0) -> None:
    """Pop every answer in ``results`` into ``out``, stamped ``t0``."""
    for rid in list(results):
        value, mode = pop(rid)
        k = rid_at.pop(rid)
        out[k] = value
        req.mode[k] = _MODES.get(mode, 2)
        req.deliver[k] = t0


def _harvest(srv, req, rid_at, clock) -> None:
    """Pop every delivered answer, point and staircase; stamp them with
    one delivery time."""
    t0 = pc()
    _collect(srv.results, srv.result_with_mode, req.answer, req, rid_at, t0)
    if req.profile is not None:
        _collect(srv.profile_results, srv.profile_result_with_mode,
                 req.profile, req, rid_at, t0)
    clock.add("result", t0, pc())


def run_open(srv, req: Requests, close: float, clock: HostClock,
             hook=(float("inf"), None)) -> None:
    """Submit each request at its due time (or as soon after as the loop
    gets to it), poll while nothing is due, collect answers as they land.
    After ``close`` (every request is then submitted) flush the remainder.
    ``hook`` is (time, fn): fn() is called once the clock passes time."""
    due, s, t, w, sub = req.due, req.s, req.t, req.w, req.submit
    kind, mixed = req.kind, req.profile is not None
    n = len(due)
    rid_at = {}
    results, profiles = srv.results, srv.profile_results
    submit, submit_profile, poll = srv.submit, srv.submit_profile, srv.poll
    hook_at, hook_fn = hook
    i = 0
    while i < n:
        now = pc()
        if now >= hook_at:
            hook_fn()
            hook_at = float("inf")
            now = pc()
        if due[i] <= now:
            while i < n and due[i] <= now:
                sub[i] = now
                if mixed and kind[i] == PROFILE:
                    rid = submit_profile(int(s[i]), int(t[i]))
                else:
                    rid = submit(int(s[i]), int(t[i]), int(w[i]))
                rid_at[rid] = i
                i += 1
                t1 = pc()
                clock.add("submit", now, t1)
                if results or profiles:
                    _harvest(srv, req, rid_at, clock)
                now = pc()
        else:
            poll()
            t1 = pc()
            clock.add("poll", now, t1)
            if results or profiles:
                _harvest(srv, req, rid_at, clock)
    req.n = n
    while pc() < close and rid_at:
        now = pc()
        poll()
        clock.add("poll", now, pc())
        if results or profiles:
            _harvest(srv, req, rid_at, clock)
    _finish(srv, req, rid_at, clock)


GRACE_S = 60.0


def _finish(srv, req, rid_at, clock) -> None:
    """Flush whatever is still queued or in flight and collect it; an
    answer that has not come a minute (GRACE_S) later is never given."""
    end = pc() + GRACE_S
    while rid_at and pc() < end:
        srv.flush()
        _harvest(srv, req, rid_at, clock)


class CompileCounter:
    """Counts the programs JAX lowers (each new shape of a jitted call,
    whether the persistent cache then serves it or XLA compiles it), and
    of those the ones the persistent cache served."""

    def __init__(self):
        import jax
        self.lowered = 0
        self.backend = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    @property
    def compiled(self) -> int:
        """Programs XLA compiled (the backend event also wraps a cache
        read)."""
        return self.backend - self.cache_hits

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.backend += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


POOL = 1 << 17        # candidate requests the warm-up batches are cut from
DRAWS = 64            # random batches per size that set a size's range


def _pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def warm_shapes(srv, src, rng, tile_cnt: np.ndarray, kinds=None) -> int:
    """Flush one batch of every shape the cell's traffic can give a flush,
    so that nothing is compiled inside the window.

    A ragged flush's program is fixed by its kind (point or profile), its
    padded batch size (the next power of two) and its worklist length:
    the batch's tile pairs (``tile_cnt[s] * tile_cnt[t]`` per request, the
    cheapest vertex's square per pad slot), rounded up to a power of two.
    For each batch size b up to ``max_batch`` the range of worklist
    lengths is read from random batches of the cell's own requests
    (between b/2 + 1 and b of them), widened by two powers of two below
    and one above; for every length in it, a batch is cut from the cell's
    requests sorted by cost so that its pairs land on that length. The
    deadline is off meanwhile, so each batch flushes whole, and the memos
    are emptied before each, since the batches share requests (the cell's
    traffic refills them after).

    A mix with profiles (``kinds``, its kind stream, draws which of the
    candidates are profiles) then gets the same grid of profile batches,
    cut from its profile requests' own pairs. A flush that holds both
    kinds dispatches its point batch and its profile batch as two
    programs, each of a shape one of the two grids has lowered. Returns
    the number of requests sent."""
    saved = srv.max_wait_us
    srv.max_wait_us = None
    s, t, w = src.draw(rng, POOL)
    kind = src.kinds(kinds, POOL)
    # one request per memo key, so that no batch rides another's slot
    key = (np.minimum(s, t).astype(np.int64) * src.V + np.maximum(s, t)
           ) * (src.W + 1) + w
    _, first = np.unique(key, return_index=True)
    ps, pt, pw = s[first], t[first], w[first]

    def points(pick):
        srv.memo.clear()   # batches share requests
        rids = [srv.submit(int(ps[i]), int(pt[i]), int(pw[i]))
                for i in pick]
        srv.flush()
        for r in rids:
            srv.result(r)

    try:
        sent = _warm_grid(srv, rng, tile_cnt, ps, pt, points)
        if kind is not None:
            sent += _warm_profiles(srv, src, rng, tile_cnt,
                                   s[kind == PROFILE], t[kind == PROFILE])
    finally:
        srv.max_wait_us = saved
    return sent


def _warm_grid(srv, rng, tile_cnt, s, t, send) -> int:
    """`warm_shapes`' grid over candidates (s, t): ``send(pick)`` flushes
    one batch of the candidates at ``pick``. Returns the requests sent."""
    cost = tile_cnt[s].astype(np.int64) * tile_cnt[t]
    order = np.argsort(cost, kind="stable")
    csum = np.concatenate([[0], np.cumsum(cost[order])])
    pad_cost = int(tile_cnt.min()) ** 2
    sent = 0
    b = 1
    while b <= srv.max_batch:
        sizes = sorted({b // 2 + 1, b})
        tot = [rng.choice(cost, (DRAWS, n)).sum(1) + (b - n) * pad_cost
               for n in sizes]
        lo = max(_pow2(min(x.min() for x in tot)) // 4, 1)
        hi = _pow2(max(x.max() for x in tot)) * 2
        p = lo
        while p <= hi:
            for n in sizes:
                # windows of n requests in cost order: their sums grow
                # with the window's start
                sums = csum[n:] - csum[:-n] + (b - n) * pad_cost
                k = int(np.searchsorted(sums, p // 2, side="right"))
                if k < len(sums) and sums[k] <= p:
                    send(order[k:k + n])
                    sent += n
                    break
            p *= 2
        b *= 2
    return sent


def _warm_profiles(srv, src, rng, tile_cnt, s, t) -> int:
    """The profile grid over the profile candidates (s, t), one per
    undirected pair. Returns the requests sent."""
    key = np.minimum(s, t).astype(np.int64) * src.V + np.maximum(s, t)
    _, first = np.unique(key, return_index=True)
    qs, qt = s[first], t[first]

    def profiles(pick):
        srv.memo.clear()   # a staircase also answers its pair's points
        srv.profile_memo.clear()
        rids = [srv.submit_profile(int(qs[i]), int(qt[i])) for i in pick]
        srv.flush()
        for r in rids:
            srv.profile_result(r)

    return _warm_grid(srv, rng, tile_cnt, qs, qt, profiles)
