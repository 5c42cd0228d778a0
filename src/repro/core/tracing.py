"""Serving tracer: spans and per-request stamps of `WCSDServer`, off by
default (docs/serving.md §1a).

One clock, `time.perf_counter_ns`, so an outside profiler anchor taken on
`time.perf_counter` puts every span on the device trace's clock with one
offset. Everything lives in preallocated ring buffers (allocated at the
first `start`): a long-running server keeps the newest entries, never
grows, and counts what the rings overwrote in ``dropped``.

What is recorded, only while on:

* per request (keyed by request id): its enqueue time, the flush that
  carried it, and whether it rode another request's batch slot (a
  duplicate of a queued or in-flight key). Memo and profile-memo hits
  carry flush ``MEMO`` and are delivered at enqueue; a request still
  queued carries ``PENDING`` until a flush takes it. A batch put back in
  the queue after a failed flush keeps its enqueue time and takes the
  flush that retries it.
* per flush: id, cause (`CAUSES`), requests ``n``, padded batch ``Q``
  and worklist length (the largest of the flush's scalar and profile
  batches).
* spans (`SPANS`), each with its flush id: ``flush.stage`` is the whole
  dispatch after the previous drain, ``engine.plan`` and
  ``engine.launch`` nest in it, ``engine.build`` nests in the launch of
  a shape the engine had not run; ``drain.wait`` and ``drain.deliver``
  belong to the drain that hands the flush's answers out. A request's
  delivery time is the end of its flush's last ``drain.deliver`` span.
"""
from __future__ import annotations

import time

import numpy as np

now = time.perf_counter_ns

SPANS = ("flush.stage", "engine.plan", "engine.launch", "engine.build",
         "drain.wait", "drain.deliver")
STAGE, PLAN, LAUNCH, BUILD, WAIT, DELIVER = range(len(SPANS))
CAUSES = ("cap", "opportunistic", "deadline", "sync")
MEMO = -1      # flush id of a request answered at enqueue
PENDING = -2   # flush id of a request no flush has taken yet

# columns of the flush and span rings
_FID, _CAUSE, _N, _Q, _WL = range(5)
_KIND, _START, _END, _PARENT = range(4)


class _Off:
    """The tracer of an engine no server has handed one: never on."""
    on = False


OFF = _Off()


class Tracer:
    """Ring-buffered spans and per-request stamps; see the module doc.

    ``requests``, ``spans`` and ``flushes`` are the ring sizes. The
    defaults hold several seconds of a server at tens of thousands of
    requests a second. ``cur`` is the flush the engines' spans belong to
    (the server sets it before it calls an engine)."""

    def __init__(self, requests: int = 1 << 17, spans: int = 1 << 16,
                 flushes: int = 1 << 14):
        self.on = False
        self.cur = PENDING
        self._sizes = (int(requests), int(spans), int(flushes))
        self._alloc(0, 0, 0)     # the rings take memory at the first start

    def _alloc(self, nreq: int, nspan: int, nflush: int) -> None:
        self._rid = np.full(nreq, -1, np.int64)
        self._enq = np.empty(nreq, np.int64)
        self._req_flush = np.empty(nreq, np.int64)
        self._rides = np.empty(nreq, bool)
        self._span = np.empty((nspan, 4), np.int64)
        self._flush = np.full((nflush, 5), -1, np.int64)
        self._n_req = self._n_span = self._n_flush = 0

    def start(self) -> None:
        if not len(self._rid):
            self._alloc(*self._sizes)
        self.on = True

    def stop(self) -> None:
        self.on = False

    def reset(self) -> None:
        """Forget everything recorded; the rings keep their memory."""
        self._n_req = self._n_span = self._n_flush = 0
        self._rid[:] = -1
        self._flush[:, _FID] = -1

    @property
    def dropped(self) -> int:
        """Entries the rings overwrote since the last `reset`."""
        return (self._n_req - int((self._rid >= 0).sum())
                + max(self._n_span - len(self._span), 0)
                + max(self._n_flush - len(self._flush), 0))

    # ------------------------------------------------------------ records
    def enqueue(self, rid: int, t_ns: int, flush: int,
                rides: bool = False) -> None:
        k = rid % len(self._rid)
        self._rid[k] = rid
        self._enq[k] = t_ns
        self._req_flush[k] = flush
        self._rides[k] = rides
        self._n_req += 1

    def carry(self, rids, flush: int) -> None:
        """The requests ``rids`` (still queued) ride flush ``flush``."""
        if not rids:
            return
        r = np.asarray(rids, np.int64)
        k = r % len(self._rid)
        k = k[self._rid[k] == r]
        self._req_flush[k] = flush

    def open_flush(self, fid: int, cause: str, n: int) -> None:
        self._flush[fid % len(self._flush)] = (fid, CAUSES.index(cause), n,
                                               0, 0)
        self._n_flush += 1
        self.cur = fid

    def shape(self, q: int, worklist_len: int) -> None:
        """The current flush's padded batch and worklist length."""
        row = self._flush[self.cur % len(self._flush)]
        if row[_FID] == self.cur:
            row[_Q] = max(row[_Q], q)
            row[_WL] = max(row[_WL], worklist_len)

    def span(self, kind: int, start_ns: int, end_ns: int,
             flush: int | None = None) -> None:
        self._span[self._n_span % len(self._span)] = (
            kind, start_ns, end_ns, self.cur if flush is None else flush)
        self._n_span += 1

    # ------------------------------------------------------------ readout
    def snapshot(self) -> dict:
        """Copies of what the rings hold, each table in order:

        ``requests`` (by rid): ``rid``, ``enqueue_ns``, ``flush``,
        ``rides``; ``flushes`` (by id): ``id``, ``cause`` (index into
        ``causes``), ``n``, ``Q``, ``worklist_len``; ``spans`` (as
        recorded): ``name`` (index into ``span_names``), ``start_ns``,
        ``end_ns``, ``flush``; and ``dropped``."""
        k = np.flatnonzero(self._rid >= 0)
        k = k[np.argsort(self._rid[k], kind="stable")]
        n, size = self._n_span, len(self._span)
        # a full ring's oldest entry sits where the next one goes
        spans = (self._span[:n].copy() if n <= size
                 else np.roll(self._span, -(n % size), axis=0))
        fl = self._flush[self._flush[:, _FID] >= 0]
        fl = fl[np.argsort(fl[:, _FID], kind="stable")]
        return {
            "requests": {"rid": self._rid[k], "enqueue_ns": self._enq[k],
                         "flush": self._req_flush[k],
                         "rides": self._rides[k]},
            "flushes": {"id": fl[:, _FID], "cause": fl[:, _CAUSE],
                        "n": fl[:, _N], "Q": fl[:, _Q],
                        "worklist_len": fl[:, _WL]},
            "spans": {"name": spans[:, _KIND], "start_ns": spans[:, _START],
                      "end_ns": spans[:, _END], "flush": spans[:, _PARENT]},
            "span_names": SPANS, "causes": CAUSES, "dropped": self.dropped,
        }


def request_times(snap: dict) -> dict:
    """Per request of a snapshot whose flush has been delivered: ``rid``,
    ``enqueue_ns``, ``stage_ns`` (its flush's ``flush.stage`` start) and
    ``deliver_ns`` (its flush's last ``drain.deliver`` end), plus
    ``rides`` and ``memo``. Memo hits have ``stage_ns == deliver_ns ==
    enqueue_ns``. Queue wait is ``stage_ns - enqueue_ns`` and flight
    ``deliver_ns - stage_ns``; a request that rode an in-flight batch
    joined after its stage began, so its queue wait is negative."""
    req, sp = snap["requests"], snap["spans"]
    fl = req["flush"]
    stage = _per_flush(sp, STAGE, first=True)
    deliver = _per_flush(sp, DELIVER, first=False)
    memo = fl == MEMO
    s_ns = np.array([stage.get(f, -1) for f in fl.tolist()], np.int64)
    d_ns = np.array([deliver.get(f, -1) for f in fl.tolist()], np.int64)
    s_ns[memo] = d_ns[memo] = req["enqueue_ns"][memo]
    done = memo | ((s_ns >= 0) & (d_ns >= 0))
    return {"rid": req["rid"][done], "enqueue_ns": req["enqueue_ns"][done],
            "stage_ns": s_ns[done], "deliver_ns": d_ns[done],
            "rides": req["rides"][done], "memo": memo[done]}


def _per_flush(sp: dict, kind: int, first: bool) -> dict:
    """flush id -> the first start (or the last end) of its ``kind``
    spans."""
    sel = sp["name"] == kind
    fids = sp["flush"][sel].tolist()
    times = (sp["start_ns"] if first else sp["end_ns"])[sel].tolist()
    out: dict = {}
    for f, t in zip(fids, times):
        if first:
            out.setdefault(f, t)
        else:
            out[f] = t
    return out
