"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own; the harness holds no table of
them. A name that resolves to no file is an error, never a default.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """A name in ``BENCHMARK.json`` (or on the command line) that leads
    nowhere, or a file that lacks a key the harness needs."""


def _load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing file: {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module, loaded by path so a
    later PR adds a family, a reference or a metric by adding a file."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"missing file: {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with its files read."""

    name: str
    chips: int
    config_name: str
    config: dict       # benchmark/configs/<config>.json
    traffic_name: str
    traffic: dict      # benchmark/traffic/<traffic>.json
    end_to_end: tuple  # metric entries of BENCHMARK.json for this cell
    per_layer: tuple

    @property
    def family(self) -> str:
        return self.config["family"]


def load_benchmark() -> dict:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _for_cell(metrics, cell_name):
    return tuple(m for m in metrics
                 if cell_name in m.get("workloads", [cell_name]))


def _stem(path: str) -> str:
    return os.path.splitext(os.path.basename(path))[0]


def _cell(bench, name, chips, config_name, config_file, traffic_file) -> Cell:
    return Cell(name=name, chips=int(chips), config_name=config_name,
                config=_load_json(config_file),
                traffic_name=_stem(traffic_file),
                traffic=_load_json(traffic_file),
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name))


def load_cell(name: str) -> Cell:
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; BENCHMARK.json has "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names config {w['config']!r}, "
                        "which BENCHMARK.json does not list")
    return _cell(bench, name, w["chips"], w["config"],
                 os.path.join(ROOT, configs[w["config"]]["file"]),
                 os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))


def cell_from_files(config_file: str, traffic_file: str, chips: int) -> Cell:
    """A cell that ``BENCHMARK.json`` does not name yet, from its two
    files (``aot_rehearsal.py --config``): named ``<config>.<traffic>``
    after them, with the metrics that every cell reports."""
    config = _stem(config_file)
    return _cell(load_benchmark(), f"{config}.{_stem(traffic_file)}", chips,
                 config, config_file, traffic_file)


def load_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``. A device that is not in
    ``benchmark/peaks.json`` is an error, not a default."""
    table = _load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SpecError(f"device_kind {device_kind!r} is not in "
                        f"benchmark/peaks.json (has {sorted(table)})")
    return table[device_kind]
