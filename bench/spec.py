"""Benchmark registry: BENCHMARK.json plus the files it names.

Everything that belongs to one configuration, one traffic mix, one cell's
correctness limits or one per-layer metric is a file of its own, found by
the name BENCHMARK.json gives it:

    bench/configs/<config>.json      model sizes as run; its "model" key
                                     names the model module
    bench/reference/<family>.py      one model module per family: the
                                     weights tree (shapes, finish) and
                                     the plain reference (logits)
    bench/traffic/<traffic>.json     traffic mix; its "kind" picks the driver
    bench/drivers/<kind>.py          one driver per traffic kind
    bench/limits/<workload>.json     correctness limits of one cell
    bench/metrics/<metric>.py        one reader per per-layer metric

A later change adds a configuration, a cell or a metric by adding files
and entries only.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
from types import ModuleType

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str) -> ModuleType:
    """Import a file by path (metric names hold dots, so not by name)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    moves: str | None = None               # per-layer: the end-to-end
    workloads: tuple[str, ...] | None = None   # metric it moves; its cells

    def applies_to(self, workload: str) -> bool:
        return self.workloads is None or workload in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of BENCHMARK.json with everything it names loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _metric(entry: dict) -> Metric:
    wl = entry.get("workloads")
    return Metric(entry["name"], entry["unit"], entry.get("moves"),
                  tuple(wl) if wl is not None else None)


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def config_path(name: str, bench: dict, root: str = ROOT) -> str:
    for c in bench["configs"]:
        if c["name"] == name:
            return os.path.join(root, c["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    for w in bench["workloads"]:
        if w["name"] == workload:
            break
    else:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    config = _load_json(config_path(w["config"], bench, root))
    bench_dir = os.path.join(root, "bench")
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    limits = _load_json(os.path.join(bench_dir, "limits",
                                     workload + ".json"))
    e2e = tuple(m for m in map(_metric, bench["end_to_end"])
                if m.applies_to(workload))
    per_layer = tuple(m for m in map(_metric, bench["per_layer"])
                      if m.applies_to(workload))
    return Cell(workload, int(w["chips"]), config, traffic, limits, e2e,
                per_layer)


def model(cfg: dict, root: str = ROOT) -> ModuleType:
    """The model module a configuration names under ``"model"``, a path
    from the repository's root: ``shapes(arch)`` and ``finish(params,
    arch)`` give the weights tree, ``logits(params, cfg, tokens, idx,
    prec)`` the plain reference."""
    if "model" not in cfg:
        raise KeyError(f"configuration {cfg.get('name')!r} names no "
                       f"\"model\" module")
    path = os.path.join(root, cfg["model"])
    if not os.path.isfile(path):
        raise FileNotFoundError(f"configuration {cfg.get('name')!r} names "
                                f"model {cfg['model']!r}, which is no file")
    name = os.path.splitext(os.path.basename(path))[0]
    return load_module(path, "bench_model_" + name.replace("-", "_"))


def driver(kind: str) -> ModuleType:
    return load_module(os.path.join(BENCH_DIR, "drivers", kind + ".py"),
                       f"bench_driver_{kind}")


def runner(kind: str):
    """How a cell of this traffic kind runs: its driver's own ``run``,
    or, for a serving driver (one that gives only ``Source`` and
    ``end_to_end``), the shared serving run."""
    drv = driver(kind)
    if hasattr(drv, "run"):
        return drv.run
    from bench import serving
    return functools.partial(serving.run, drv)


def metric_reader(name: str) -> ModuleType:
    return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_"))


def peaks(device_kind: str) -> dict:
    """Published peaks of the chip; a chip not in the table is an error."""
    table = _load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]
