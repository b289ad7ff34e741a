"""BENCHMARK.json and the data files it names.

Everything that belongs to one configuration, one traffic mix, one
cell, one per-layer metric or one kernel family is a file of its own,
found by the name BENCHMARK.json gives it:

    configs/<config>.json    the deployment as it is run
    traffic/<traffic>.json   parameters for the one load generator
    cells/<workload>.json    optional: state a cell sets up beyond its
                             configuration and traffic (an OSD lost)
    metrics/<metric>.py      one reader: SOURCE, LAYER, MOVES, read(ctx)
    references/<code>.py     the plain reference of one erasure code:
                             shards_of(obj, profile, stripe_unit)
    kernels/<family>.json    XLA module-name patterns of one program
                             family, and whether it is GF work
    chain_keys.json          the cache-key prefixes of the compiled GF
                             chains set-up warms, each with where its
                             key states the chunks one call takes in
    peaks.json               published peaks by device_kind
"""
import functools
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def benchmark() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with the files it names, loaded."""

    def __init__(self, name: str, bench: dict = None):
        bench = bench or benchmark()
        rows = [w for w in bench["workloads"] if w["name"] == name]
        if not rows:
            known = ", ".join(w["name"] for w in bench["workloads"])
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json "
                             f"has: {known}")
        self.bench = bench
        self.row = rows[0]
        self.name = name
        self.chips = self.row["chips"]
        cfg_row = [c for c in bench["configs"]
                   if c["name"] == self.row["config"]][0]
        self.config = _load(os.path.join(ROOT, cfg_row["file"]))
        self.traffic = _load(os.path.join(
            BENCH_DIR, "traffic", self.row["traffic"] + ".json"))
        extra = os.path.join(BENCH_DIR, "cells", name + ".json")
        self.state = _load(extra) if os.path.exists(extra) else {}

    def _listed(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if self._listed(m)]

    def per_layer(self) -> list:
        return [m for m in self.bench["per_layer"] if self._listed(m)]


def _module(kind: str, path: str):
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The module ``metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"per-layer metric {name!r} has no reader "
                         f"at {path}")
    return _module("metric", path)


def reference_name(config: dict) -> str:
    """A configuration's plain reference is ``<technique>_w<w>`` of its
    pool's profile."""
    profile = config["pool"]["profile"]
    return f"{profile['technique']}_w{profile['w']}"


@functools.lru_cache(maxsize=None)
def reference(name: str):
    """The module ``references/<name>.py``."""
    d = os.path.join(BENCH_DIR, "references")
    path = os.path.join(d, name + ".py")
    if not os.path.exists(path):
        have = sorted(f[:-3] for f in os.listdir(d) if f.endswith(".py"))
        raise SystemExit(
            f"erasure code reference {name!r} is not in "
            f"benchmark/references (has {have}); add its plain "
            f"reference there, there is no default")
    return _module("reference", path)


def kernel_families() -> list:
    d = os.path.join(BENCH_DIR, "kernels")
    return [_load(os.path.join(d, f)) for f in sorted(os.listdir(d))
            if f.endswith(".json")]


def chain_keys() -> dict:
    """{cache-key prefix: where the key states its columns}."""
    return _load(os.path.join(BENCH_DIR, "chain_keys.json"))["prefixes"]


def peaks(device_kind: str) -> dict:
    table = _load(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(has {sorted(table)}); add its published peaks with their "
            f"source, there is no default")
    return table[device_kind]
