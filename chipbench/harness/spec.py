"""Finds a cell's parts by name: configuration, traffic mix, metric readers.

Everything that belongs to one configuration, one traffic mix or one
metric lives in a file of its own under the benchmark directory:

    configs/<config>.json     widths, dtype, cache geometry, check limits
    traffic/<traffic>.json    arrival rate, length distributions
    metrics/<metric>.py       one reader: ``read(run, trace) -> float | None``
    reference/<name>.py       the plain reference a configuration names
    peaks.json                published peaks by ``device_kind``

A metric ``a.b.c`` is read by ``metrics/a.b.c.py`` if it exists, else by
``metrics/a.b.py``, else ``metrics/a.py``: one reader serves a quantity
that ``BENCHMARK.json`` splits by suffix across cells.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A cell, configuration, traffic mix or metric that cannot be found."""


def load_json(path: Path) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                    f"{[c['name'] for c in bench['workloads']]}")


def cell_metrics(bench: dict, cell: str, section: str) -> list:
    """The entries of ``section`` ("end_to_end" or "per_layer") that this
    cell reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def load_config(name: str, base: Path = BENCH_DIR) -> dict:
    cfg = load_json(Path(base) / "configs" / f"{name}.json")
    cfg.setdefault("name", name)
    return cfg


def load_traffic(name: str, base: Path = BENCH_DIR) -> dict:
    mix = load_json(Path(base) / "traffic" / f"{name}.json")
    mix.setdefault("name", name)
    return mix


def load_peaks(device_kind: str, base: Path = BENCH_DIR) -> dict:
    table = load_json(Path(base) / "peaks.json")
    try:
        return table[device_kind]
    except KeyError:
        raise SpecError(f"device kind {device_kind!r} has no entry in "
                        f"peaks.json (have {sorted(table)})") from None


def _load_module(path: Path, tag: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{tag}_{path.stem.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, base: Path = BENCH_DIR):
    """The ``read(run, trace)`` function for metric ``name``."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = Path(base) / "metrics" / (".".join(parts[:n]) + ".py")
        if path.exists():
            return _load_module(path, "metric").read
    raise SpecError(f"no reader for metric {name!r} under {base}/metrics")


def reference_module(name: str, base: Path = BENCH_DIR):
    return _load_module(Path(base) / "reference" / f"{name}.py", "reference")
