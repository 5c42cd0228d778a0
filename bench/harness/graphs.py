"""The configurations' graphs, made from the configuration and a graph seed.

The generators are copies of the two families the paper's analogues use
(a road-like grid with sparse diagonal shortcuts, and a Barabási–Albert
social graph), each edge carrying one of ``num_levels`` quality values.
They are kept here so that the data a cell serves cannot change when the
program's own generators do. The edge list goes to the program (which
builds its index from it) and to the reference (which answers from it),
so the two share nothing else.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class EdgeList:
    """Undirected edges, each listed once; ``qual`` holds quality values."""
    num_nodes: int
    u: np.ndarray
    v: np.ndarray
    qual: np.ndarray


def _qualities(num_edges: int, num_levels: int, rng, skew: float = 0.0):
    vals = np.arange(1.0, num_levels + 1.0)
    if skew <= 0:
        probs = np.full(num_levels, 1.0 / num_levels)
    else:
        probs = 1.0 / (np.arange(1, num_levels + 1) ** skew)
        probs /= probs.sum()
    return rng.choice(vals, size=num_edges, p=probs)


def road_grid(rows: int, cols: int, num_levels: int, diag_prob: float,
              seed: int) -> EdgeList:
    """rows x cols grid plus diagonal shortcuts kept with ``diag_prob``."""
    rng = np.random.default_rng(seed)
    idx = np.arange(rows * cols).reshape(rows, cols)
    us = [idx[:, :-1].ravel(), idx[:-1, :].ravel()]
    vs = [idx[:, 1:].ravel(), idx[1:, :].ravel()]
    if diag_prob > 0:
        du, dv = idx[:-1, :-1].ravel(), idx[1:, 1:].ravel()
        keep = rng.random(len(du)) < diag_prob
        us.append(du[keep])
        vs.append(dv[keep])
    u, v = np.concatenate(us), np.concatenate(vs)
    return EdgeList(rows * cols, u, v, _qualities(len(u), num_levels, rng))


def scale_free(num_nodes: int, m: int, num_levels: int, skew: float,
               seed: int) -> EdgeList:
    """Barabási–Albert graph with ``m`` edges per new vertex; qualities
    skewed towards the low levels by ``skew``."""
    import networkx as nx
    e = np.array(nx.barabasi_albert_graph(num_nodes, m, seed=seed).edges(),
                 dtype=np.int64)
    rng = np.random.default_rng(seed + 1)
    return EdgeList(num_nodes, e[:, 0], e[:, 1],
                    _qualities(len(e), num_levels, rng, skew=skew))


GENERATORS = {"road_grid": road_grid, "scale_free": scale_free}


def make_graph(config: dict) -> EdgeList:
    """The configuration's graph: its generator, parameters and
    ``graph_seed``."""
    g = dict(config["graph"])
    return GENERATORS[g.pop("generator")](seed=int(config["graph_seed"]),
                                          **g)
