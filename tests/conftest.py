import os
import sys

import pytest

# the suite runs on the CPU backend with interpret-mode kernels, also on a
# TPU host (the chip path is chip_smoke.py); set before any test imports jax
os.environ["JAX_PLATFORMS"] = "cpu"

# src layout import without install; tests dir for the _hypo_shim helper
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))


@pytest.fixture(scope="session")
def built_indices():
    """Session-cached index construction for parametrized serving/profile
    tests: every case that needs "a built index over graph X" shares one
    construction per distinct (generator, kwargs) key instead of paying
    the build per parametrization — the profile suite runs its whole
    layout x kernel matrix against two builds, not a dozen.

    The cache keys on the graph VERSION as well as the generator kwargs:
    a dynamic test that mutates a cached graph (`mutate_edges` bumps
    ``version``) gets a fresh (graph, index) pair instead of poisoning the
    static suite's fixture — and the static suite never sees an index that
    was built over a mutated graph (regression-locked in
    tests/test_dynamic.py)."""
    cache = {}

    def get(family: str, **kwargs):
        from repro.core import generators
        from repro.core.wc_index import build_wc_index
        key = (family, tuple(sorted(kwargs.items())))
        if key in cache:
            g, idx, built_version = cache[key]
            if getattr(g, "version", 0) == built_version:
                return g, idx
        g = getattr(generators, family)(**kwargs)
        idx = build_wc_index(g, ordering="degree")
        cache[key] = (g, idx, getattr(g, "version", 0))
        return g, idx

    return get


@pytest.fixture(scope="session")
def serve_layout():
    """Label-store layout for layout-agnostic serving tests.

    Defaults to "padded"; the CI matrix exports REPRO_LABEL_LAYOUT=csr to
    run the same tests against the CSR-packed store + segmented query path.
    Tests that assert layout-specific behavior (e.g. flush padding) pin
    their layout explicitly instead of using this fixture.
    """
    layout = os.environ.get("REPRO_LABEL_LAYOUT", "padded")
    assert layout in ("padded", "csr"), layout
    return layout
