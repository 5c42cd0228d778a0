"""A whole run of each tiny cell on the CPU: sound runs come out correct,
and each fault planted under the timed path comes out not correct."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import BENCH, CHECKOUT

UNREACHABLE = 1 << 30


def run(bench, name, seed=2**31 + 99, seconds=1.0, traced=False):
    import jax

    from harness.cell_run import run_cell
    cell = bench.cell(name)
    out, checks = run_cell(bench, cell, seed, seconds, traced,
                           time.perf_counter(), jax.devices()[:cell.chips],
                           "cpu")
    json.dumps(out)               # the result line is plain JSON
    return out


@pytest.mark.parametrize("name", ["tiny-open", "tiny-zipf", "tiny-open-4"])
def test_sound_run_is_correct(tiny_bench, quick, name):
    out = run(tiny_bench, name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {m["name"] for m in
                                   tiny_bench.cell(name).end_to_end}
    assert out["device"]["count"] == tiny_bench.cell(name).chips


def test_traced_run_reports_per_layer_metrics(tiny_bench, quick):
    out = run(tiny_bench, "tiny-zipf", traced=True)
    assert out["correct"], out["checks"]
    # the CPU has no device plane: no idle share, no roofline
    assert set(out["metrics"]) == {
        m["name"] for m in tiny_bench.cell("tiny-zipf").per_layer} - {
        "idle_share.lat", "ragged_roofline.lat"}


def patch_flush(monkeypatch, spoil):
    """Spoil every flush's answers where the engine produces them."""
    from repro.core import query
    orig = query.ShardedQueryEngine.query_async

    def query_async(self, s, t, w):
        handle = orig(self, s, t, w)
        finalize = handle._finalize
        padded = self._ragged_pad(len(s))
        handle._finalize = lambda: spoil(np.array(finalize()), padded,
                                         self.ndev)
        return handle

    monkeypatch.setattr(query.ShardedQueryEngine, "query_async", query_async)


def altered(out, padded, ndev):
    out[0] += 1
    return out


def half_left_out(out, padded, ndev):
    out[len(out) // 2:] = UNREACHABLE
    return out


def other_chips_left_out(out, padded, ndev):
    out[padded // ndev:] = UNREACHABLE
    return out


@pytest.mark.parametrize("spoil,name", [
    (altered, "tiny-open"), (half_left_out, "tiny-zipf"),
    (other_chips_left_out, "tiny-open-4")],
    ids=["answer-altered", "half-batch-left-out", "other-chips-left-out"])
def test_fault_in_the_flush_is_caught(tiny_bench, quick, monkeypatch, spoil,
                                      name):
    patch_flush(monkeypatch, spoil)
    out = run(tiny_bench, name)
    assert not out["correct"]
    assert out["checks"]["wrong"]["value"] > 0


def test_fault_in_the_memo_is_caught(tiny_bench, quick, monkeypatch):
    from repro.core.serve import WCSDServer
    orig = WCSDServer._memo_put
    monkeypatch.setattr(WCSDServer, "_memo_put",
                        lambda self, key, value: orig(self, key, value + 1))
    out = run(tiny_bench, "tiny-zipf")
    assert not out["correct"]


def test_lost_request_is_caught(tiny_bench, quick, monkeypatch):
    """The server hands out a request id and drops every 50th request."""
    from harness import drive
    from repro.core.serve import WCSDServer
    orig = WCSDServer.submit

    def submit(self, s, t, w):
        if self._next_rid % 50 == 49:
            self._next_rid += 1
            return self._next_rid - 1
        return orig(self, s, t, w)

    monkeypatch.setattr(WCSDServer, "submit", submit)
    monkeypatch.setattr(drive, "warm_shapes", lambda *a: 0)
    monkeypatch.setattr(drive, "GRACE_S", 0.5)
    out = run(tiny_bench, "tiny-open")
    assert not out["correct"]
    assert out["checks"]["lost"]["value"] == out["failed"] > 0


def cli(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_non_zero_and_prints_nothing():
    p = cli(["--workload", "road-uniform", "--seed", "3", "--seconds", "1",
             "--trace", "0"], CHECKOUT)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_exits_non_zero(tmp_path):
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = cli(["--workload", "road-uniform", "--seed", "3", "--seconds", "1",
             "--trace", "0"], tmp_path)
    assert p.returncode != 0 and p.stdout == ""
