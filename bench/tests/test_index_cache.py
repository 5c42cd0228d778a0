"""The first run in a checkout builds the configuration's index; later
runs load it; a change to what decides the index builds anew."""
import numpy as np

from conftest import CHECKOUT, TINY_ROAD
from harness import index_cache


def test_built_once_then_loaded(tmp_path):
    bench_dir = str(tmp_path)
    assert index_cache.ensure_index(CHECKOUT, bench_dir, TINY_ROAD) > 0
    assert index_cache.ensure_index(CHECKOUT, bench_dir, TINY_ROAD) == 0
    idx = index_cache.load_index(CHECKOUT, bench_dir, TINY_ROAD)
    assert idx.num_nodes == 144 and isinstance(idx.order, np.memmap)


def test_key_follows_graph_and_seed(tmp_path):
    path = index_cache.cache_path(CHECKOUT, str(tmp_path), TINY_ROAD)
    other = dict(TINY_ROAD, graph=dict(TINY_ROAD["graph"], diag_prob=0.1))
    assert index_cache.cache_path(CHECKOUT, str(tmp_path), other) != path
    assert index_cache.cache_path(CHECKOUT, str(tmp_path),
                                  dict(TINY_ROAD, graph_seed=1)) != path
    assert path == index_cache.cache_path(CHECKOUT, str(tmp_path),
                                          dict(TINY_ROAD))
