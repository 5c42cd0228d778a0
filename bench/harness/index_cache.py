"""Build a configuration's index once per checkout, load it after that.

The host builder takes about 100 s on the 10,000-vertex road graph, and
every run of every check would pay it. So the built index is saved with
the program's own persistence (``checkpoint/ckpt.save_packed_index``) under
``bench/.cache/index/`` and loaded with ``load_packed_index(mmap=True)``.
The file name carries a digest of everything that decides its contents:
the configuration's graph, graph seed and ordering, and the sources
that build and persist it. A change to any of them builds anew.
"""
from __future__ import annotations

import hashlib
import json
import os
import time

from .graphs import make_graph

BUILD_SOURCES = ("src/repro/core/graph.py", "src/repro/core/ordering.py",
                 "src/repro/core/dominance.py", "src/repro/core/wc_index.py",
                 "src/repro/checkpoint/ckpt.py", "bench/harness/graphs.py")


def cache_path(checkout: str, bench_dir: str, config: dict) -> str:
    h = hashlib.sha256()
    h.update(json.dumps({"graph": config["graph"], "seed": config["graph_seed"],
                         "ordering": config["ordering"]},
                        sort_keys=True).encode())
    for rel in BUILD_SOURCES:
        with open(os.path.join(checkout, rel), "rb") as f:
            h.update(rel.encode() + b"\0" + f.read())
    return os.path.join(bench_dir, ".cache", "index",
                        f"{config['name']}-{h.hexdigest()[:16]}.wcx")


def ensure_index(checkout: str, bench_dir: str, config: dict) -> float:
    """Build and save the configuration's index unless it is cached.
    Returns the seconds spent building (0.0 when it was cached)."""
    path = cache_path(checkout, bench_dir, config)
    if os.path.exists(path):
        return 0.0
    from repro.checkpoint.ckpt import save_packed_index
    from repro.core.graph import Graph
    from repro.core.wc_index import as_packed_index, build_wc_index
    t0 = time.perf_counter()
    e = make_graph(config)
    g = Graph.from_edges(e.num_nodes, e.u, e.v, e.qual)
    idx = as_packed_index(build_wc_index(g, ordering=config["ordering"]))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    save_packed_index(path, idx)
    return time.perf_counter() - t0


def load_index(checkout: str, bench_dir: str, config: dict):
    from repro.checkpoint.ckpt import load_packed_index
    idx, _ = load_packed_index(cache_path(checkout, bench_dir, config),
                               mmap=True)
    return idx
