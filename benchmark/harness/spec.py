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
import re

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


#: What a key of ``reduced`` may never be: a width (model-configs guide,
#: section 4). How many layers, experts, rows of the vocabulary, heads
#: or groups of heads a chip holds is a count and may be its share; how
#: wide one is (a hidden, intermediate, latent, state or projection
#: size, anything ending in ``_dim`` or ``_rank``, a head's size, a
#: convolution's kernel, a chunk, a window, an expansion factor) and how
#: many experts a token takes are the model, and no cut touches them.
WIDTH_RE = re.compile(
    r"hidden_size|intermediate|latent|state_size|proj|_dim$|_rank$"
    r"|head_size|expan|per_tok|kernel|chunk|window")
#: The guide's floors for a share: experts held, and the eighth of the
#: vocabulary.
MIN_EXPERTS_HELD = 8
MIN_VOCAB_SHARE = 8


def is_width(key: str) -> bool:
    return bool(WIDTH_RE.search(key))


def check_cuts(config: dict, where: str = "the configuration") -> None:
    """Hold ``config["reduced"]`` to the guide's rule on cuts: a count
    may be the chip's share, a width never. Where the file states what
    was ``published``, every reduced key is there, every share (all but
    the depth) divides its published count, the floors hold and
    ``deployment`` says over how many chips each layer is divided.
    Raises :class:`SpecError` naming the key."""
    reduced = config.get("reduced", [])
    widths = [k for k in reduced if is_width(k)]
    if widths:
        raise SpecError(
            f"{where} lists {widths} in 'reduced': a width is never cut "
            "(how many heads, experts, layers or vocabulary rows a chip "
            "holds may be; how wide one is may not)")
    if "published" not in config:
        return
    published = config["published"]
    if not str(config.get("deployment", "")).strip():
        raise SpecError(f"{where} has 'published' counts and no "
                        "'deployment' that says which chips share a layer")
    for key in reduced:
        if key not in published:
            raise SpecError(f"{where} reduces {key!r} and 'published' "
                            "does not give its count")
        held, whole = config.get(key), published[key]
        if not (isinstance(held, int) and 1 <= held <= whole):
            raise SpecError(f"{where} holds {key} = {held!r} of the "
                            f"published {whole!r}")
        if "layers" not in key and whole % held:
            raise SpecError(f"{where}: the published {key} = {whole} is no "
                            f"whole multiple of the {held} held")
        if key.endswith("experts") and held < MIN_EXPERTS_HELD:
            raise SpecError(f"{where} holds {held} of {key}: the floor is "
                            f"{MIN_EXPERTS_HELD} routed experts a layer")
        if key == "vocab_size" and held * MIN_VOCAB_SHARE < whole:
            raise SpecError(f"{where} holds {held} of {whole} vocabulary "
                            f"rows: the floor is 1/{MIN_VOCAB_SHARE}")
    heads = {"num_attention_heads", "num_key_value_heads"}
    if heads & set(reduced) and heads <= set(config):
        q, kv = config["num_attention_heads"], config["num_key_value_heads"]
        if kv < 1 or q % kv:
            raise SpecError(f"{where} holds {q} query heads over {kv} "
                            "key-value heads: want a whole multiple of at "
                            "least one key-value head")


def load_benchmark() -> dict:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _for_cell(metrics, cell_name):
    return tuple(m for m in metrics
                 if cell_name in m.get("workloads", [cell_name]))


def _stem(path: str) -> str:
    return os.path.splitext(os.path.basename(path))[0]


def _cell(bench, name, chips, config_name, config_file, traffic_file) -> Cell:
    config = _load_json(config_file)
    check_cuts(config, os.path.relpath(config_file, ROOT))
    return Cell(name=name, chips=int(chips), config_name=config_name,
                config=config,
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
