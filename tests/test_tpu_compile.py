"""The serving kernels compile for a TPU v5e chip that is described, not
attached.

Interpret mode accepts block shapes, broadcasts and scalar-memory sizes
that the Mosaic compiler refuses, so the interpret-mode tests elsewhere
cannot see a kernel that would never run on the chip. Each test here
lowers and compiles one kernel (or the fused plan + launch jit) for one
chip of a described ``v5e:2x2`` at the production lane width and checks
that the program holds a Mosaic ``tpu_custom_call``.

The topology is described inside a module-scoped fixture — never while a
module is imported — because only one process at a time may load the TPU
library, and every test worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core.query import ragged_profile_batch, ragged_query_batch
from repro.kernels import wcsd_query as wq

LANE = 128
T = 52_859          # arena tiles of road_grid(100, 100), the smoke index
Q = 4097            # a max_batch flush plus the worklist trash row
W = 5               # quality levels
V = 10_000          # vertices of road_grid(100, 100)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _arena(sh, compressed):
    dtypes = ((jnp.int16, jnp.bfloat16, jnp.int8) if compressed
              else (jnp.int32,) * 3)
    return tuple(_shape(sh, (T, LANE), d) for d in dtypes) \
        + (_shape(sh, (T,)),) * 2


@pytest.mark.parametrize("compressed", [False, True],
                         ids=["plain", "compressed"])
@pytest.mark.parametrize("profile", [False, True], ids=["query", "profile"])
def test_ragged_kernel_compiles(one_chip, profile, compressed):
    WL = wq.ragged_launch_capacity(compressed)       # one full launch
    args = _arena(one_chip, compressed) + (_shape(one_chip, (WL,)),) * 3
    if profile:
        def fn(*a):
            return wq.wcsd_profile_ragged(*a, num_rows=Q, num_levels=W,
                                          interpret=False)
    else:
        args += (_shape(one_chip, (Q,)),)

        def fn(*a):
            return wq.wcsd_query_ragged(*a, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("profile", [False, True], ids=["query", "profile"])
def test_segmented_kernel_compiles(one_chip, profile):
    B = 4096
    side_s = (_shape(one_chip, (1024, 256)),) * 3
    side_t = (_shape(one_chip, (512, 768)),) * 3
    rows = (_shape(one_chip, (B,)),) * (2 if profile else 3)
    if profile:
        def fn(*a):
            return wq.wcsd_profile_segmented(*a, num_levels=W,
                                             interpret=False)
    else:
        def fn(*a):
            return wq.wcsd_query_segmented(*a, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, *side_s, *side_t, *rows)


@pytest.mark.parametrize("launches", [1, 2])
@pytest.mark.parametrize("profile", [False, True], ids=["query", "profile"])
def test_ragged_flush_jit_compiles_within_smem(one_chip, profile, launches):
    """The fused plan + launch jit an engine runs per flush, at the largest
    worklist that fits ONE launch's scalar memory, and at twice that —
    which must split into two launches, each of which still compiles."""
    cap = wq.ragged_launch_capacity()
    WL = launches * cap
    assert wq.ragged_launches(WL) == (launches, cap)
    args = _arena(one_chip, False) + (_shape(one_chip, (V,)),) * 2 \
        + (_shape(one_chip, (2 if profile else 3, Q - 1)),)
    kw = dict(worklist_len=WL, interpret=False, use_kernel=True)
    if profile:
        def fn(*a):
            return ragged_profile_batch(*a, num_levels=W, **kw)
    else:
        def fn(*a):
            return ragged_query_batch(*a, **kw)
    text = _compiled_text(fn, *args)
    assert text.count('custom_call_target="tpu_custom_call"') == launches
