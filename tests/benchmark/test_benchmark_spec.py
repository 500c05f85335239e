"""``BENCHMARK.json`` against the files it names, the command's refusals,
and the promise that a later PR adds a cell with data files alone."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import spec, step  # noqa: E402

BENCH = spec.load_benchmark()
NAME_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def _run(root, *args, env=None):
    full = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        capture_output=True, text=True, timeout=300, env=full, cwd=root)


def test_top_level_keys_are_the_contracts():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_names_are_plain_and_used_once():
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in BENCH[key]]
    assert all(NAME_RE.match(n) for n in names), names
    assert len(names) == len(set(names))
    for x in BENCH["configs"] + BENCH["workloads"]:
        assert len(x["why"]) <= 200, (x["name"], len(x["why"]))
    # A pair of config and traffic appears once, whatever the chips.
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_the_four_chip_cell_runs_the_one_chip_cells_traffic():
    """``bert_base_s512_x4`` has a traffic file of its own only because a
    pair appears once; scaling efficiency is its throughput over
    ``bert_base_s512``'s, so the parameters stay the same."""
    one = dict(spec.load_cell("bert_base_s512").traffic)
    four = dict(spec.load_cell("bert_base_s512_x4").traffic)
    four.pop("note")
    assert one == four


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = spec.load_cell(name)
    assert cell.chips in (1, 4)
    assert not [k for k in step.TRAFFIC_KEYS if k not in cell.traffic]
    for kind in ("families", "reference"):
        assert spec.load_module(kind, cell.family)
    family = spec.load_module("families", cell.family)
    assert family.ITEM in ("images", "tokens")
    assert family.model_flops_per_item(cell.config, cell.traffic) > 0
    assert callable(spec.load_module("reference", cell.family).loss)
    # Every cell reports set-up, another end-to-end metric and a
    # per-layer metric.
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    # Each limit of ``correct`` that the file sets says what it was set
    # from.
    assert cell.config["loss_tolerance"]["why"]
    # (``null`` for a number that is said and not compared.)
    update = cell.config["update_tolerance"]
    assert update["why"] and {"rel", "pooled_rel"} <= set(update)
    assert all(limit is None or 0 < limit < 1
               for limit in (update["rel"], update["pooled_rel"]))


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_file_is_under_paths_and_used(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    assert config["reduced"] == entry["reduced"]
    assert entry["source"].startswith("https://")
    assert name in {w["config"] for w in BENCH["workloads"]}
    # No width is ever cut; a count may be the chip's share, and where
    # the file states what was published the share is held to it.
    assert not [k for k in entry["reduced"] if spec.is_width(k)]
    spec.check_cuts(config, entry["file"])


COUNTS = ["num_hidden_layers", "num_experts", "n_routed_experts",
          "vocab_size", "num_attention_heads", "num_key_value_heads",
          "mamba_num_heads", "n_groups", "hidden_dropout_prob",
          "attention_probs_dropout_prob", "type_vocab_size"]
WIDTHS = ["hidden_size", "intermediate_size", "moe_intermediate_size",
          "shared_expert_intermediate_size", "head_dim", "v_head_dim",
          "qk_rope_head_dim", "mamba_head_dim", "kv_lora_rank",
          "ssm_state_size", "moe_latent_size", "conv_kernel", "chunk_size",
          "sliding_window", "num_experts_per_tok"]


@pytest.mark.parametrize("key", COUNTS)
def test_a_count_may_be_listed_in_reduced(key):
    """Layers, experts, vocabulary rows, heads of every kind and their
    groups are counts: a chip may hold its share (model-configs guide,
    section 4). ``bert_base``'s three are switches, not sizes."""
    assert not spec.is_width(key)
    spec.check_cuts({"reduced": [key], key: 4})


@pytest.mark.parametrize("key", WIDTHS)
def test_a_width_is_never_listed_in_reduced(key):
    """How wide a head, an expert, a latent, a state, a convolution, a
    chunk or a window is, and how many experts a token takes, are the
    model: no cut touches them, with ``published`` stated or without."""
    assert spec.is_width(key)
    for config in ({"reduced": [key], key: 64},
                   {"reduced": ["num_hidden_layers", key], key: 64,
                    "num_hidden_layers": 4, "deployment": "4 chips a layer",
                    "published": {key: 128, "num_hidden_layers": 48}}):
        with pytest.raises(spec.SpecError, match="a width is never cut"):
            spec.check_cuts(config)


def _share(**changes):
    """A configuration that holds a fourth of its heads, groups and
    experts and an eighth of its vocabulary, with ``changes`` applied
    (``None`` deletes a key; ``published.x`` reaches into the group)."""
    config = {
        "num_hidden_layers": 5, "num_experts": 128, "vocab_size": 16384,
        "num_attention_heads": 8, "num_key_value_heads": 2,
        "mamba_num_heads": 32, "n_groups": 2, "head_dim": 128,
        "published": {"num_hidden_layers": 88, "num_experts": 512,
                      "vocab_size": 131072, "num_attention_heads": 32,
                      "num_key_value_heads": 8, "mamba_num_heads": 128,
                      "n_groups": 8},
        "deployment": "4 chips share each mixer by heads, 4 the experts",
    }
    config["reduced"] = list(config["published"])
    for key, value in changes.items():
        group, _, inner = key.rpartition(".")
        target = config[group] if group else config
        if value is None:
            del target[inner]
        else:
            target[inner] = value
    return config


@pytest.mark.parametrize("changes,refusal", [
    ({}, None),
    # the depth is a cut, not a share: 88 is no multiple of 5
    ({"num_hidden_layers": 7}, None),
    ({"num_experts": 8, "vocab_size": 131072 // 8}, None),
    ({"num_key_value_heads": 1, "num_attention_heads": 4}, None),
    ({"deployment": None}, "no 'deployment'"),
    ({"deployment": "  "}, "no 'deployment'"),
    ({"published.mamba_num_heads": None}, "does not give its count"),
    ({"mamba_num_heads": 48}, "no whole multiple"),
    ({"n_groups": 3}, "no whole multiple"),
    ({"vocab_size": 131072 // 3}, "no whole multiple"),
    ({"num_attention_heads": 64}, "of the published 32"),
    ({"num_key_value_heads": 0}, "of the published 8"),
    ({"num_experts": 4}, "the floor is 8 routed experts"),
    ({"vocab_size": 131072 // 16}, "the floor is 1/8"),
    ({"num_attention_heads": 2, "num_key_value_heads": 4},
     "a whole multiple of at least one key-value head"),
])
def test_a_share_is_held_to_what_was_published(changes, refusal):
    config = _share(**changes)
    if refusal is None:
        spec.check_cuts(config)
    else:
        with pytest.raises(spec.SpecError, match=refusal):
            spec.check_cuts(config, "scratch.json")


def test_a_configuration_without_published_counts_states_no_share():
    """``bert_base`` and ``resnet50`` cut no count: nothing to hold them
    to but the rule on widths."""
    for name in ("bert_base", "resnet50"):
        with open(os.path.join(REPO, "benchmark", "configs",
                               name + ".json")) as f:
            config = json.load(f)
        assert "published" not in config
        spec.check_cuts(config)


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_a_reader(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert callable(spec.load_module("metrics", name).read)
    assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert entry["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert set(entry.get("workloads", CELLS)) <= set(CELLS)


def test_end_to_end_metrics_and_the_share_of_four_chip_cells():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] == 0.1
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_rehearsal_takes_a_cell_by_its_files_or_by_its_name(capsys):
    """``aot_rehearsal.py --config --traffic --chips`` is for a cell that
    has no entry yet; given an existing cell's files it resolves what the
    name does, but the name and the metrics that list their cells."""
    from benchmark import aot_rehearsal

    (named,) = aot_rehearsal.resolve(["bert_base_s512"])
    (by_files,) = aot_rehearsal.resolve([
        "--config", os.path.join(REPO, "benchmark/configs/bert_base.json"),
        "--traffic", os.path.join(REPO, "benchmark/traffic/seq512_bs16.json"),
        "--chips", "1"])
    assert named == spec.load_cell("bert_base_s512")
    assert by_files.name == "bert_base.seq512_bs16"
    for field in ("chips", "config_name", "config", "traffic_name",
                  "traffic", "family", "end_to_end"):
        assert getattr(by_files, field) == getattr(named, field), field
    assert {m["name"] for m in by_files.per_layer} == {
        m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    # No argument: every cell of BENCHMARK.json, in its order.
    assert [c.name for c in aot_rehearsal.resolve([])] == CELLS
    for bad in (["--config", "x.json"], ["bert_base_s512", "--chips", "1"]):
        with pytest.raises(SystemExit):
            aot_rehearsal.resolve(bad)
    assert "go together" in capsys.readouterr().err
    with pytest.raises(spec.SpecError, match="missing file"):
        aot_rehearsal.resolve(["--config", "no_such.json", "--traffic",
                               "no_such.json", "--chips", "1"])


def test_peaks_name_their_source_and_an_unknown_kind_is_an_error():
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    assert "Google Cloud" in peaks["source"]
    v5e = spec.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError, match="not in benchmark/peaks.json"):
        spec.load_peaks("TPU v9 imaginary")


def test_importing_the_benchmark_initialises_no_backend():
    code = ("import benchmark.harness.loop, benchmark.harness.layers\n"
            "import benchmark.harness.hlo, benchmark.aot_rehearsal\n"
            "from benchmark.harness import spec\n"
            "for k, n in [('families', 'resnet'), ('reference', 'resnet'),\n"
            "             ('families', 'transformer_lm'),\n"
            "             ('metrics', 'flash_roofline')]:\n"
            "    spec.load_module(k, n)\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "print('NO BACKEND')\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO, env=dict(os.environ, PYTHONPATH=REPO))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "NO BACKEND" in proc.stdout


def test_command_refuses_to_run_off_a_tpu():
    proc = _run(REPO, "--workload", CELLS[0], "--seed", "0",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode not in (0, 2), proc.stderr[-2000:]
    assert "no TPU" in proc.stderr and "platform='cpu'" in proc.stderr
    assert '"correct"' not in proc.stdout  # no result line


def test_unknown_workload_is_an_error_that_lists_the_cells():
    proc = _run(REPO, "--workload", "no_such_cell", "--seed", "0",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode == 2
    assert "unknown workload" in proc.stderr and CELLS[0] in proc.stderr
    assert not proc.stdout.strip()


def _copy_of_the_benchmark(tmp_path, bench=BENCH):
    """``benchmark/`` and a ``BENCHMARK.json`` in a temporary root."""
    root = str(tmp_path / "copy")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_a_fifth_cell_is_one_json_file_and_one_entry(tmp_path):
    """In a temporary copy: a new traffic file from an existing one, one
    new entry in ``workloads``, no other edit. The copy's command then
    finds the cell (it gets as far as looking for the chip), and a cell
    whose traffic file is missing is refused by name."""
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"] += [
        {"name": "fifth", "config": "bert_base",
         "traffic": "seq512_bs16_int8_sharded", "chips": 1, "why": "test"},
        {"name": "sixth", "config": "bert_base",
         "traffic": "never_written", "chips": 1, "why": "test"}]
    root = _copy_of_the_benchmark(tmp_path, bench)
    traffic = os.path.join(root, "benchmark", "traffic")
    with open(os.path.join(traffic, "seq512_bs16.json")) as f:
        mix = json.load(f)
    mix.update(compression="int8", sharded_update=True)
    with open(os.path.join(traffic, "seq512_bs16_int8_sharded.json"),
              "w") as f:
        json.dump(mix, f)

    args = ("--seed", "0", "--seconds", "1", "--trace", "0")
    found = _run(root, "--workload", "fifth", *args)
    assert found.returncode not in (0, 2), found.stderr[-2000:]
    assert "no TPU" in found.stderr
    missing = _run(root, "--workload", "sixth", *args)
    assert missing.returncode == 2
    assert "benchmark/traffic/never_written.json" in missing.stderr


def _scratch_cell(tmp_path, reduced, **config_changes):
    """``bert_base``'s file with ``reduced`` and the changes, beside an
    existing traffic file: a cell by its two files."""
    with open(os.path.join(REPO, "benchmark/configs/bert_base.json")) as f:
        config = json.load(f)
    config.update(config_changes, reduced=reduced)
    path = tmp_path / "scratch.json"
    path.write_text(json.dumps(config))
    return str(path), os.path.join(REPO,
                                   "benchmark/traffic/seq512_bs16.json")


def test_a_scratch_configuration_may_hold_its_share_of_heads(tmp_path):
    files = _scratch_cell(tmp_path, ["num_attention_heads"],
                          num_attention_heads=3,
                          published={"num_attention_heads": 12})
    cell = spec.cell_from_files(*files, 1)
    assert cell.config["num_attention_heads"] == 3
    # The same with a head's size is refused, by the rule and by name.
    files = _scratch_cell(tmp_path, ["head_dim"], head_dim=32,
                          published={"head_dim": 64})
    with pytest.raises(spec.SpecError, match=r"\['head_dim'\].*never cut"):
        spec.cell_from_files(*files, 1)


@pytest.mark.parametrize("key,held,published,resolves", [
    ("num_attention_heads", 3, 12, True), ("head_dim", 32, 64, False)])
def test_the_command_takes_a_share_of_heads_and_refuses_a_width(
        tmp_path, key, held, published, resolves):
    """In a temporary copy whose ``bert_base`` lists ``key`` in
    ``reduced``: a head count gets as far as looking for the chip, a
    head's size is refused before anything is imported."""
    root = _copy_of_the_benchmark(tmp_path)
    path = os.path.join(root, "benchmark", "configs", "bert_base.json")
    with open(path) as f:
        config = json.load(f)
    config.update({key: held, "published": {key: published},
                   "reduced": [key]})
    with open(path, "w") as f:
        json.dump(config, f)
    proc = _run(root, "--workload", "bert_base_s512", "--seed", "0",
                "--seconds", "1", "--trace", "0")
    assert '"correct"' not in proc.stdout
    if resolves:
        assert proc.returncode not in (0, 2), proc.stderr[-2000:]
        assert "no TPU" in proc.stderr
    else:
        assert proc.returncode == 2, proc.stderr[-2000:]
        assert "a width is never cut" in proc.stderr and key in proc.stderr
