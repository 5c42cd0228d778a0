"""Find a cell's configuration, traffic mix, metric readers and peaks by
the names ``BENCHMARK.json`` gives them."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, resolved."""
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]   # the end-to-end metrics this cell reports
    per_layer: list[dict]    # the per-layer metrics this cell reports


class Bench:
    """The benchmark rooted at ``checkout`` (``BENCHMARK.json`` there, the
    data files under ``bench_dir``)."""

    def __init__(self, checkout: str = CHECKOUT, bench_dir: str | None = None,
                 spec: dict | None = None):
        self.checkout = checkout
        self.bench_dir = bench_dir or os.path.join(checkout, "bench")
        if spec is None:
            with open(os.path.join(checkout, "BENCHMARK.json")) as f:
                spec = json.load(f)
        self.spec = spec

    def _data(self, kind: str, name: str) -> dict:
        if not NAME_RE.match(name):
            raise ValueError(f"bad {kind} name {name!r}")
        with open(os.path.join(self.bench_dir, kind, name + ".json")) as f:
            return json.load(f)

    def config(self, name: str) -> dict:
        entry = next(c for c in self.spec["configs"] if c["name"] == name)
        with open(os.path.join(self.checkout, entry["file"])) as f:
            return json.load(f)

    def mix(self, name: str) -> dict:
        return self._data("traffic", name)

    def cell(self, name: str) -> Cell:
        try:
            w = next(w for w in self.spec["workloads"] if w["name"] == name)
        except StopIteration:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json"
                           ) from None
        e2e = [m for m in self.spec["end_to_end"]
               if name in m.get("workloads", [name])]
        moved = {m["name"] for m in e2e}
        layer = [m for m in self.spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
        return Cell(name=name, chips=int(w["chips"]),
                    config=self.config(w["config"]),
                    mix=self.mix(w["traffic"]),
                    end_to_end=e2e, per_layer=layer)

    def reader(self, metric: str):
        """The ``read(run)`` function of a metric: ``metrics/<name>.py``."""
        path = os.path.join(self.bench_dir, "metrics", metric + ".py")
        if not os.path.exists(path):
            raise FileNotFoundError(f"no reader for metric {metric!r} under "
                                    f"{self.bench_dir}/metrics")
        modname = "bench_metric_" + re.sub(r"\W", "_", metric)
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def peaks(self, device_kind: str) -> dict:
        """Peaks of one chip; a device missing from the table is an error."""
        with open(os.path.join(self.bench_dir, "peaks.json")) as f:
            table = json.load(f)
        if device_kind not in table["devices"]:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           "bench/peaks.json")
        return table["devices"][device_kind]
