"""Find a cell's configuration, traffic mix and per-layer metrics by name.

`BENCHMARK.json` at the checkout's root lists the cells and metrics; each
configuration is `bench/configs/<name>.json`, each traffic mix
`bench/traffic/<name>.json`, each per-layer metric's reader
`bench/metrics/<name>.py` (a module with `read(ctx) -> float | None`) and
each plain reference, which a configuration names under `reference`, the
module `bench.references.<name>` (with `WEIGHTS`, `run` and
`observables`).
A new cell, mix or metric is a new file and a new entry, never an edit.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os


def _path(root, *parts) -> str:
    return os.path.join(os.fspath(root), *parts)


def load_benchmark(root) -> dict:
    with open(_path(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(known: {[w['name'] for w in bench['workloads']]})")


def _load_json(root, kind: str, name: str) -> dict:
    with open(_path(root, "bench", kind, f"{name}.json")) as fh:
        return json.load(fh)


def load_config(root, name: str) -> dict:
    return _load_json(root, "configs", name)


def load_traffic(root, name: str) -> dict:
    return _load_json(root, "traffic", name)


def load_metric(root, name: str):
    """The reader module of per-layer metric `name`."""
    path = _path(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}",
                                                  path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(name: str):
    """The plain reference module that a configuration names."""
    return importlib.import_module(f"bench.references.{name}")
