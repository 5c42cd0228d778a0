"""The one traffic generator. A mix is a data file (``bench/traffic/<mix>
.json``) of parameters that this module reads. Requests are due on a
schedule (an open loop), whatever the server does:

- ``rate_per_s``: the mean arrival rate. The window holds exactly
  ``round(rate * seconds)`` requests at sorted uniform times, which is a
  Poisson process conditioned on its count, so every seed offers the same
  amount of work;
- ``pairs``: ``"uniform"`` (s and t uniform over the vertices) or
  ``"zipf"`` (s and t each drawn Zipf(``zipf_a``) over a permutation of the
  vertices that the seed draws: the popular vertices differ per seed);
- ``levels``: ``"uniform"``, w uniform over the graph's quality levels;
- ``profile_share`` (0 when absent): the share of requests that are
  profile requests, each asking for the whole staircase ``dist(s, t, w)``
  at every level 0..W (`WCSDServer.submit_profile`). A profile request
  keeps its drawn s and t; its w is not used. Exactly
  ``round(share * n)`` of a draw's n requests are profiles, so every seed
  offers the same work; which ones comes from a stream of its own
  (``kind``), so s, t and w are drawn exactly as in a mix without
  profiles.

Everything comes from ``--seed`` through named streams, so the window's
requests, the warm-up's and the correctness sample never share draws.
"""
from __future__ import annotations

import numpy as np

STREAMS = {"window": 1, "warmup": 2, "sample": 3, "popularity": 4,
           "kind": 5}
POINT, PROFILE = 0, 1


def rng_for(seed: int, stream: str, of: str | None = None
            ) -> np.random.Generator:
    """The generator of a named stream; ``of`` names the stream whose
    requests it serves (``rng_for(seed, "kind", "window")`` draws the
    window's request kinds)."""
    key = [int(seed) & 0xFFFFFFFFFFFFFFFF, STREAMS[stream]]
    if of is not None:
        key.append(STREAMS[of])
    return np.random.default_rng(key)


class PairSource:
    """Draws (s, t, w) requests of one mix over one graph."""

    def __init__(self, mix: dict, num_nodes: int, num_levels: int, seed: int):
        self.mix = mix
        self.V = int(num_nodes)
        self.W = int(num_levels)
        if mix["pairs"] == "zipf":
            perm = rng_for(seed, "popularity").permutation(self.V)
            ranks = np.arange(1, self.V + 1, dtype=np.float64)
            cdf = np.cumsum(ranks ** -float(mix["zipf_a"]))
            self._cdf = cdf / cdf[-1]
            self._perm = perm
        elif mix["pairs"] != "uniform":
            raise ValueError(f"unknown pairs {mix['pairs']!r}")
        if mix["levels"] != "uniform":
            raise ValueError(f"unknown levels {mix['levels']!r}")
        self.profile_share = float(mix.get("profile_share", 0.0))
        if not 0.0 <= self.profile_share <= 1.0:
            raise ValueError(f"profile_share {self.profile_share} is not "
                             "in [0, 1]")

    def _vertices(self, rng, n: int) -> np.ndarray:
        if self.mix["pairs"] == "uniform":
            return rng.integers(0, self.V, n)
        rank = np.searchsorted(self._cdf, rng.random(n), side="right")
        return self._perm[np.minimum(rank, self.V - 1)]

    def draw(self, rng, n: int):
        s = self._vertices(rng, n).astype(np.int32)
        t = self._vertices(rng, n).astype(np.int32)
        w = rng.integers(0, self.W, n).astype(np.int32)
        return s, t, w

    def kinds(self, rng, n: int) -> np.ndarray | None:
        """The kinds (POINT or PROFILE) of n requests drawn from the kind
        stream ``rng``; None, with nothing drawn, for a mix without
        profiles."""
        if self.profile_share <= 0.0:
            return None
        if rng is None:
            raise ValueError("a mix with profiles needs a kind stream")
        kind = np.full(n, POINT, np.int8)
        kind[rng.permutation(n)[:round(self.profile_share * n)]] = PROFILE
        return kind


def open_schedule(mix: dict, seconds: float, rng) -> np.ndarray:
    """Due times (seconds from the window's start) of a mix."""
    n = int(round(float(mix["rate_per_s"]) * seconds))
    return np.sort(rng.random(n) * seconds)
