"""The plain reference: w-constrained shortest hop distances by breadth-
first search over the benchmark's own edge list, with scipy's graph
routines and nothing of the program.

Semantics, as the configurations state them: the quality values of the
edges, sorted and made distinct, are the levels 0..W-1; a request (s, t, w)
asks for the fewest hops from s to t using only edges whose level is at
least w; s == t is 0 hops; no such path reads ``UNREACHABLE``.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import shortest_path

UNREACHABLE = 1 << 30


class Reference:
    def __init__(self, edges):
        self.V = int(edges.num_nodes)
        self.levels, lvl = np.unique(np.asarray(edges.qual, np.float64),
                                     return_inverse=True)
        self._u = np.asarray(edges.u, np.int64)
        self._v = np.asarray(edges.v, np.int64)
        self._lvl = lvl
        self._adj = {}

    @property
    def num_levels(self) -> int:
        return len(self.levels)

    def _graph(self, w: int):
        if w not in self._adj:
            keep = self._lvl >= w
            u, v = self._u[keep], self._v[keep]
            ones = np.ones(2 * len(u), np.int8)
            self._adj[w] = sp.csr_matrix(
                (ones, (np.concatenate([u, v]), np.concatenate([v, u]))),
                shape=(self.V, self.V))
        return self._adj[w]

    def distances(self, s, t, w) -> np.ndarray:
        """Reference answers to the requests (s[i], t[i], w[i])."""
        s, t, w = (np.asarray(a, np.int64) for a in (s, t, w))
        out = np.full(len(s), UNREACHABLE, np.int64)
        for lev in np.unique(w):
            at = np.flatnonzero(w == lev)
            if lev >= self.num_levels:
                out[at] = np.where(s[at] == t[at], 0, UNREACHABLE)
                continue
            src, row = np.unique(s[at], return_inverse=True)
            d = shortest_path(self._graph(int(lev)), method="D",
                              unweighted=True, indices=src)
            got = d[row, t[at]]
            out[at] = np.where(np.isfinite(got), got, UNREACHABLE)
        return out

    def control(self, s, t, w) -> np.ndarray:
        """The control: the reference with the quality guarantee broken by
        one level (w - 1 in place of w), which is what serving a coarser
        or stale level table would answer."""
        return self.distances(s, t, np.maximum(np.asarray(w) - 1, 0))
