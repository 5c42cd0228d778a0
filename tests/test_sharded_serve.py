"""ShardedQueryEngine + async WCSDServer on multi-device meshes.

The bit-for-bit acceptance test runs in a subprocess with 8 virtual host
devices (the device count must be fixed before jax initializes; the main
pytest process keeps its default single device) by invoking the same
`launch.dryrun --serve` entry point CI runs, so the test and the CI step
cannot drift apart. In-process tests cover the engine's code paths on a
1-device mesh and the row-gather collective math that the vertex-sharded
fallback rests on.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.generators import scale_free
from repro.core.query import DeviceQueryEngine, ShardedQueryEngine
from repro.core.serve import WCSDServer
from repro.core.wc_index import build_wc_index
from repro.launch.mesh import make_serving_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="module")
def small_index():
    return build_wc_index(scale_free(150, 3, num_levels=4, seed=12),
                          ordering="degree")


def _queries(idx, n, seed):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, idx.num_nodes, n).astype(np.int32),
            rng.integers(0, idx.num_nodes, n).astype(np.int32),
            rng.integers(0, idx.num_levels, n).astype(np.int32))


# --------------------------------------------------- in-process (1 device)
@pytest.mark.parametrize("layout", ["padded", "csr"])
@pytest.mark.parametrize("budget", [None, 1])
def test_sharded_engine_single_device_mesh(small_index, layout, budget):
    """Both placements (replicated / sharded_labels) degenerate gracefully
    to a 1-device mesh and agree with the single-device engine exactly."""
    mesh = make_serving_mesh()
    eng = ShardedQueryEngine(small_index, mesh=mesh, layout=layout,
                             device_budget_bytes=budget)
    assert eng.mode == ("replicated" if budget is None else "sharded_labels")
    s, t, wl = _queries(small_index, 300, seed=3)
    exp = np.asarray(DeviceQueryEngine(small_index,
                                       layout=layout).query(s, t, wl))
    got = np.asarray(eng.query(s, t, wl))
    assert np.array_equal(got, exp)


def test_sharded_engine_rejects_bad_args(small_index):
    with pytest.raises(ValueError):
        ShardedQueryEngine(small_index, mesh=make_serving_mesh(),
                           layout="nope")
    with pytest.raises(ValueError):
        ShardedQueryEngine(small_index, mesh=make_serving_mesh(),
                           layout="csr", cap=4)


def test_sharded_server_single_device_mesh(small_index):
    srv = WCSDServer(small_index, max_batch=32, backend="sharded",
                     layout="csr", mesh=make_serving_mesh())
    s, t, wl = _queries(small_index, 150, seed=5)
    got = srv.query_many(s, t, wl)
    assert np.array_equal(got, small_index.query_batch(s, t, wl))
    assert len(srv.results) == 0      # read-once delivery drained


# ------------------------------------------------- subprocess (8 devices)
def test_dryrun_serve_eight_virtual_devices():
    """Acceptance: the CI dryrun — ShardedQueryEngine (replicated AND
    vertex-sharded, single- and multi-pod meshes) + async WCSDServer on 8
    virtual host devices, bit-for-bit against the single-device engine on
    differential-harness instances."""
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)   # dryrun sets the device count itself
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--serve", "--quick"],
        capture_output=True, text=True, env=env, timeout=420)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "serve dryrun PASS on 8 virtual devices" in r.stdout
    assert r.stdout.count("bit-identical") >= 8  # 2 instances x 4 modes
    # the profile path is part of the same acceptance sweep
    assert r.stdout.count("queries + profiles bit-identical") >= 8
    assert "(+profiles)" in r.stdout             # async server epoch


def test_row_gather_collectives_eight_devices():
    """row_gather_psum / row_gather_psum_scatter: exact gather from a
    block-row-sharded array, replicated and scattered forms."""
    prog = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import (row_gather_psum,
                                           row_gather_psum_scatter)
from repro.launch.mesh import make_serving_mesh
mesh = make_serving_mesh()
V, W, B = 64, 16, 32
rng = np.random.default_rng(0)
store = rng.integers(-5, 100, (V, W)).astype(np.int32)
rows = rng.integers(0, V, B).astype(np.int32)
per = V // 8
f = jax.jit(jax.shard_map(
    lambda sh, rr: row_gather_psum(sh, rr, ("data",), per),
    mesh=mesh, in_specs=(P("data", None), P(None)), out_specs=P(None)))
np.testing.assert_array_equal(np.asarray(f(store, rows)), store[rows])
g = jax.jit(jax.shard_map(
    lambda sh, rr: row_gather_psum_scatter(sh, rr, ("data",), per),
    mesh=mesh, in_specs=(P("data", None), P(None)), out_specs=P("data")))
np.testing.assert_array_equal(np.asarray(g(store, rows)), store[rows])
print("OK row gather")

# fused multi-array gather (ONE reduce-scatter for hub/dist/wlev + a
# count column) == per-array gathers, exactly
from repro.distributed.collectives import multi_row_gather_psum_scatter
store2 = rng.integers(0, 7, (V, 3)).astype(np.int32)
col = rng.integers(1, 50, (V, 1)).astype(np.int32)
m = jax.jit(jax.shard_map(
    lambda a, b, c, rr: multi_row_gather_psum_scatter(
        (a, b, c), rr, ("data",), per),
    mesh=mesh, in_specs=(P("data", None),) * 3 + (P(None),),
    out_specs=(P("data"),) * 3))
ga, gb, gc = (np.asarray(x) for x in m(store, store2, col, rows))
np.testing.assert_array_equal(ga, store[rows])
np.testing.assert_array_equal(gb, store2[rows])
np.testing.assert_array_equal(gc, col[rows])
print("OK fused multi row gather")

# ServeConfig.multi_pod reaches the engine's mesh (regression: the flag
# used to be dropped by server_kwargs)
from repro.configs.wcsd_serve import ServeConfig
from repro.core.serve import WCSDServer
from repro.core.generators import scale_free
from repro.core.wc_index import build_wc_index
idx = build_wc_index(scale_free(60, 3, num_levels=3, seed=1))
srv = WCSDServer(idx, **ServeConfig(multi_pod=True, max_batch=32).server_kwargs())
assert srv.engine.mesh.axis_names == ("pod", "data"), srv.engine.mesh
s = np.arange(30, dtype=np.int32)
assert np.array_equal(srv.query_many(s, s, np.zeros(30, np.int32)),
                      np.zeros(30, np.int32))
print("OK multi_pod config plumb")
"""
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "OK row gather" in r.stdout
    assert "OK fused multi row gather" in r.stdout
    assert "OK multi_pod config plumb" in r.stdout
