"""Harness self-tests, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/tests

They run the whole harness at tiny sizes (interpret-mode kernels, four
virtual devices for the four-chip path); no number from them is a device
number."""
import json
import os
import shutil
import sys

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
CHECKOUT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(CHECKOUT, "src"))
sys.path.insert(0, BENCH)

TINY_ROAD = {"name": "tiny-road", "graph": {"generator": "road_grid",
             "rows": 12, "cols": 12, "num_levels": 3, "diag_prob": 0.05},
             "graph_seed": 0, "ordering": "degree",
             "serve": {"max_batch": 64}}
TINY_SOCIAL = {"name": "tiny-social", "graph": {"generator": "scale_free",
               "num_nodes": 150, "m": 3, "num_levels": 4, "skew": 0.8},
               "graph_seed": 0, "ordering": "degree",
               "serve": {"max_batch": 64}}
MIXES = {
    "open-uniform": {"rate_per_s": 400, "pairs": "uniform",
                     "levels": "uniform"},
    "open-zipf": {"rate_per_s": 400, "pairs": "zipf", "zipf_a": 1.0,
                  "levels": "uniform"},
}
CELLS = [("tiny-open", "tiny-road", "open-uniform", 1),
         ("tiny-zipf", "tiny-social", "open-zipf", 1),
         ("tiny-open-4", "tiny-road", "open-uniform", 4)]


@pytest.fixture(scope="session")
def tiny_bench(tmp_path_factory):
    """A benchmark of tiny cells in a directory of its own, found through
    the same `Bench` lookups as the real one; its metric readers and peaks
    are the real ones."""
    from harness.spec import Bench
    root = tmp_path_factory.mktemp("bench")
    for sub in ("configs", "traffic"):
        (root / sub).mkdir()
    shutil.copytree(os.path.join(BENCH, "metrics"), root / "metrics")
    shutil.copy(os.path.join(BENCH, "peaks.json"), root / "peaks.json")
    for cfg in (TINY_ROAD, TINY_SOCIAL):
        (root / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    for name, mix in MIXES.items():
        (root / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        real = json.load(f)
    def tiny(m):
        # every tiny cell reports every metric the real cells report
        if "workloads" not in m:
            return m
        return dict(m, workloads=sorted(n for n, _, _, _ in CELLS))

    spec = {
        "configs": [{"name": c["name"],
                     "file": str(root / "configs" / f"{c['name']}.json")}
                    for c in (TINY_ROAD, TINY_SOCIAL)],
        "workloads": [{"name": n, "config": c, "traffic": m, "chips": k}
                      for n, c, m, k in CELLS],
        "end_to_end": [tiny(m) for m in real["end_to_end"]],
        "per_layer": [tiny(m) for m in real["per_layer"]],
    }
    return Bench(CHECKOUT, bench_dir=str(root), spec=spec)


@pytest.fixture
def quick(monkeypatch):
    """Short warm-up for the tiny cells."""
    from harness import cell_run
    monkeypatch.setattr(cell_run, "WARM_TRAFFIC_S", 0.5)
    monkeypatch.setattr(cell_run, "WARM_SLICE_S", 0.3)
    monkeypatch.setattr(cell_run, "TRACE_S", 0.5)
