"""Find what a cell is made of, by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  Each lives in a file of
its own, found by name:

* the configuration: the ``file`` its ``configs`` entry gives
  (``benchmark/configs/<config>.json``);
* the traffic mix: ``benchmark/traffic/<traffic>.json``, parameters that
  the one general loop in ``benchmark/traffic.py`` reads;
* the system generator and its plain reference: the module
  ``benchmark/systems/<system>.py`` that the configuration names;
* a per-layer metric: ``benchmark/metrics/<metric>.py``, a reader with
  ``read(ctx)`` that returns the number or None.

So a new cell, mix, configuration or metric is new files and new entries
in ``BENCHMARK.json``; no file of the harness changes.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A name that ``BENCHMARK.json`` or the files under it do not hold."""


def load() -> dict:
    try:
        with open(ROOT / "BENCHMARK.json") as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"no BENCHMARK.json at {ROOT}") from None


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json; cells: "
                    f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(ROOT / c["file"]) as f:
                conf = json.load(f)
            if conf.get("name") != name:
                raise SpecError(f"{c['file']} names {conf.get('name')!r}, "
                                f"not {name!r}")
            return conf
    raise SpecError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    path = BENCH_DIR / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic mix file {path}")
    with open(path) as f:
        return json.load(f)


def _module(path: Path, label: str) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"no {label} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{label}_{path.stem.replace('.', '_')}", path)
    if spec.name in sys.modules:
        return sys.modules[spec.name]
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod      # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def local(name: str) -> ModuleType:
    """A module of the harness itself, ``benchmark/<name>.py``, loaded
    by path (``trace`` would otherwise find the standard library's)."""
    return _module(BENCH_DIR / f"{name}.py", "harness")


def system(name: str) -> ModuleType:
    return _module(BENCH_DIR / "systems" / f"{name}.py", "system")


def reader(name: str) -> ModuleType:
    return _module(BENCH_DIR / "metrics" / f"{name}.py", "metric")


def _applies(metric: dict, cell_name: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def end_to_end(bench: dict, cell_name: str) -> list:
    """The end-to-end metrics a cell reports."""
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell_name in m["workloads"]]


def per_layer(bench: dict, cell_name: str) -> list:
    """The per-layer metrics a cell reports: those that list it, and
    those without a list that move an end-to-end metric it reports."""
    e2e = {m["name"] for m in end_to_end(bench, cell_name)}
    return [m for m in bench["per_layer"] if _applies(m, cell_name, e2e)]
