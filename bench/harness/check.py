"""Decide ``correct``: compare what the window's timed path delivered with
the reference, and check the server's delivery guarantees.

A sample of the delivered answers, drawn from the seed, is compared with
`Reference.distances`. It is drawn per origin, so each kind of answer the
server gives is covered: answers computed by a device flush, answers from
the memo, and duplicates that rode an earlier identical request's batch
slot. The device answers include the requests whose label rows are the
longest (the most tile pairs, the most launches). Profile requests have
the same three origins of their own (``profile_device``, ``profile_memo``,
``profile_dup``), drawn after the point requests' from the same stream,
and each sampled staircase is compared at every level 0..W. Every number
compared is printed with its limit.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .traffic import POINT, PROFILE, rng_for

SAMPLE = {"device": 2000, "memo": 1000, "dup": 1000,
          "profile_device": 1000, "profile_memo": 500, "profile_dup": 500}
LONGEST = 64


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float
    at_least: bool = False

    @property
    def ok(self) -> bool:
        return self.value >= self.limit if self.at_least \
            else self.value <= self.limit

    def as_json(self) -> dict:
        return {"value": self.value, "limit": self.limit,
                "kind": "min" if self.at_least else "max"}


def duplicates(s, t, w, submit, deliver, V: int, W: int,
               kind=None) -> np.ndarray:
    """True where a request was submitted while an earlier request with the
    same (undirected) key was still unanswered: the server piggybacks it
    on that request's batch slot instead of computing it again. A point
    request's key is its pair and level; a profile request's (``kind``)
    is its pair alone, as the server keys its staircases."""
    n = len(s)
    if n == 0:
        return np.zeros(0, bool)
    lo, hi = np.minimum(s, t).astype(np.int64), np.maximum(s, t)
    key = (lo * V + hi) * (W + 1) + w
    if kind is not None and np.any(kind == PROFILE):
        key = np.where(kind == PROFILE, lo * V + hi, key) * 2 + kind
    order = np.lexsort((np.arange(n), key))
    k = key[order]
    first = np.concatenate([[True], k[1:] != k[:-1]])
    gid = np.cumsum(first) - 1
    # times as integer ns from the first submit; never answered = latest
    t0 = np.nanmin(submit)
    sub = np.round((submit[order] - t0) * 1e9).astype(np.int64)
    dl = deliver[order]
    late = int(np.round((np.nanmax(np.concatenate([deliver, submit])) - t0)
                        * 1e9)) + 1
    dl = np.where(np.isnan(dl), late, np.round((dl - t0) * 1e9)
                  ).astype(np.int64)
    # running max of delivery times within each key group: offsetting each
    # group above the last keeps one accumulate from crossing groups
    big = late + 1
    run = np.maximum.accumulate(dl + gid * big) - gid * big
    prev = np.concatenate([[-1], run[:-1]])
    prev[first] = -1
    out = np.zeros(n, bool)
    out[order] = prev > sub
    return out


def sample(origin: dict, work: np.ndarray, seed: int) -> np.ndarray:
    """Indices to compare: up to SAMPLE[name] drawn from each origin's
    indices in the dict's order, plus the LONGEST device answers of each
    kind by ``work``."""
    rng = rng_for(seed, "sample")
    pick = []
    for name, idx in origin.items():
        k = min(SAMPLE[name], len(idx))
        pick.append(rng.choice(idx, k, replace=False) if k else idx[:0])
    for name in ("device", "profile_device"):
        dev = origin.get(name, ())
        if len(dev):
            pick.append(dev[np.argsort(-work[dev], kind="stable")[:LONGEST]])
    return np.unique(np.concatenate(pick)).astype(np.int64)


def judge(req, n: int, ref, dup: np.ndarray, work: np.ndarray, seed: int,
          server: dict) -> tuple[list[Check], dict]:
    """The checks of one run, and the sample's counts by origin.
    ``server`` holds mode, retries, demotions as the run left them."""
    got = ~np.isnan(req.deliver[:n])
    mode = req.mode[:n]
    kind = (req.kind[:n] if req.kind is not None
            else np.full(n, POINT, np.int8))
    point = kind == POINT
    origin = {"device": np.flatnonzero(got & point & (mode != 1) & ~dup),
              "memo": np.flatnonzero(got & point & (mode == 1)),
              "dup": np.flatnonzero(got & point & (mode != 1) & dup)}
    profiles = req.profile is not None and not point.all()
    if profiles:
        prof = got & ~point
        origin.update(
            profile_device=np.flatnonzero(prof & (mode != 1) & ~dup),
            profile_memo=np.flatnonzero(prof & (mode == 1)),
            profile_dup=np.flatnonzero(prof & (mode != 1) & dup))
    picked = sample(origin, work, seed)
    idx, pidx = picked[point[picked]], picked[~point[picked]]
    want = ref.distances(req.s[idx], req.t[idx], req.w[idx])
    wrong = int(np.count_nonzero(req.answer[idx] != want))
    counts = {k: int(np.isin(picked, v).sum()) for k, v in origin.items()}
    checks = [
        Check("wrong", wrong, 0),
        Check("lost", int(n - got.sum()), 0),
        Check("checked", len(idx), min(1000, int((got & point).sum())),
              at_least=True),
        Check("off_primary", int(np.count_nonzero(got & (mode == 2))), 0),
        Check("retries", server["retries"], 0),
        Check("demotions", server["demotions"], 0),
        Check("mode_primary", int(server["mode"] == "primary"), 1,
              at_least=True),
    ]
    if profiles:
        checks += [
            Check("profile_wrong", staircase_wrong(req, pidx, ref), 0),
            Check("profile_checked", len(pidx),
                  min(500, int((got & ~point).sum())), at_least=True)]
    return checks, counts


def staircase_wrong(req, idx, ref) -> int:
    """Levels, over the staircases of requests ``idx``, at which the
    answer differs from the reference; level W (``ref.num_levels``) is 0
    for s == t and unreachable otherwise."""
    levels = ref.num_levels + 1
    want = ref.distances(np.repeat(req.s[idx], levels),
                         np.repeat(req.t[idx], levels),
                         np.tile(np.arange(levels), len(idx)))
    return int(np.count_nonzero(req.profile[idx].ravel() != want))
