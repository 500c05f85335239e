"""The ``sambay_lm`` family's FLOPs against the jaxpr's matmuls at a tiny
size and against a count by hand at the cell's, the two new roofline
counts by hand, the five new metrics on a hand-built capture, the new
cell's files and metrics by name, and the configuration: every published
number, the cut, the parameter total from the model's own shapes."""

import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark.harness import layers, spec  # noqa: E402
import test_benchmark_decoder as decoder_tests  # noqa: E402
import tiny_sambay_cell  # noqa: E402
from test_benchmark_flops import _matmul_flops  # noqa: E402

CELL = "phi4_mini_flash_v8_s8192"
CONFIG = "phi4_mini_flash_v8"
CONFIG_FILE = "benchmark/configs/phi4_mini_flash_v8.json"
family = spec.load_module("families", "sambay_lm")

TINY = dict(tiny_sambay_cell.SAMBAY, compute_dtype="float32")
TRAFFIC = dict(seq_len=32, attention="flash", remat=False)
NEW = ("sel_scan_ms_per_step", "sel_scan_roofline", "attn_diff_ms_per_step",
       "gmu_ms_per_step", "diff_attn_roofline")


def test_forward_flops_equal_the_jaxprs_matmuls_and_the_hand_counts(
        monkeypatch):
    """The jaxpr shows every product but the attention's (here a stand-in
    without products; by hand, visible pairs x two maps a pair of query
    heads, each 2 d for its scores and 2 x 2 d against the value pair).
    The selective scan multiplies no matrix and the tied head's product
    is in it once."""
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setattr(
        fa, "flash_attention",
        lambda q, k, v, causal, window: jnp.zeros_like(q))
    model = family.make_model(TINY, TRAFFIC)
    key = jax.random.PRNGKey(0)
    params, extra = family.init_variables(model, key, TINY, TRAFFIC)
    assert extra == {}
    samples, s = 2, 32
    batch = family.make_batch(key, samples, TINY, TRAFFIC)
    counted = _matmul_flops(jax.make_jaxpr(
        lambda p: family.loss_fn(model, p, extra, batch)[0])(params).jaxpr)
    assert family.kinds(TINY) == "MSMFGX"
    d, query_pairs = 8, 2
    per_pair = query_pairs * 2 * (2 * d + 2 * 2 * d)
    assert family.attention_flops_per_pair(TINY) == per_pair == 192
    full = s * (s + 1) // 2
    band = sum(min(i + 1, 16) for i in range(s))
    assert (family.visible_pairs(s), family.visible_pairs(s, 16)) == (
        full, band)
    attention = samples * per_pair * (band + 2 * full)
    assert (family.forward_flops_per_item(TINY, TRAFFIC) * samples * s
            == counted + attention)
    assert family.model_flops_per_item(TINY, TRAFFIC) == 3 * \
        family.forward_flops_per_item(TINY, TRAFFIC)


def test_the_cell_is_1_53_gflop_a_token_forward():
    cell = spec.load_cell(CELL)
    forward = family.forward_flops_per_item(cell.config, cell.traffic)
    mlp = 3 * 2 * 2560 * 10240
    selective = (2 * 2560 * 10240 + 2 * 5120 * 192 + 2 * 160 * 5120
                 + 2 * 5120 * 2560)
    qkv_and_out = 2 * 2560 * 5120 + 2 * 2560 * 2560
    memory_unit = 2 * 2 * 2560 * 5120
    query_and_out = 2 * 2 * 2560 * 2560
    # 20 pairs of query heads, two maps each: 2 x 64 + 2 x 128 a map
    per_pair = 20 * 2 * (2 * 64 + 2 * 128)
    full = per_pair * 8193 / 2
    band = per_pair * (512 * 513 // 2 + 7680 * 512) / 8192
    head = 2 * 2560 * 25008
    assert (mlp, selective, qkv_and_out, memory_unit, query_and_out,
            per_pair, head) == (157_286_400, 82_247_680, 39_321_600,
                                52_428_800, 26_214_400, 15_360, 128_040_960)
    assert forward == (6 * mlp + 2 * selective + 2 * qkv_and_out
                       + memory_unit + query_and_out + 2 * full + band
                       + head)
    assert forward == pytest.approx(1.5270e9, rel=1e-4)
    # 37.5 TFLOP a step of 8,192 tokens, forward and backward
    assert family.model_flops_per_item(cell.config, cell.traffic) * 8192 \
        == pytest.approx(37.53e12, rel=1e-3)
    # by shapes: the MLPs 62%, the mixers' projections 21%, the maps 9%
    assert 6 * mlp / forward == pytest.approx(0.618, abs=2e-3)
    assert (2 * full + band) / forward == pytest.approx(0.0874, abs=2e-3)


def test_roofline_counts_by_hand_at_the_cells_shape():
    cell = spec.load_cell(CELL)
    peaks = spec.load_peaks("TPU v5 lite")
    scan = spec.load_module("metrics", "sel_scan_roofline")
    # 671 M state updates a layer a pass, seven operations each
    assert scan.scan_flops(8192, 5120, 16) == 3 * 671_088_640 * 7
    # x, dt, B, C twice; y, dy; dx, ddt, dB, dC: 41,056 elements a token
    assert scan.scan_bytes(8192, 5120, 16) == 2 * 8192 * 41_056
    floor = scan.floor_seconds(2, cell.config, cell.traffic, peaks)
    # bytes-bound: 0.821 ms of bytes against 0.072 ms of FLOPs a layer
    assert floor == pytest.approx(2 * 2 * 8192 * 41_056 / 819e9)
    assert floor / 2 == pytest.approx(0.8213e-3, rel=1e-3)
    assert 3 * 671_088_640 * 7 / 197e12 == pytest.approx(0.0715e-3,
                                                          rel=1e-3)

    maps = spec.load_module("metrics", "diff_attn_roofline")
    full_pairs, band_pairs = 8192 * 8193 // 2, 512 * 513 // 2 + 7680 * 512
    assert (full_pairs, band_pairs) == (33_558_528, 4_063_488)
    # a map: four products at 64 and three at 128, 2 FLOPs each: 1,280;
    # two maps for each of 20 pairs of query heads
    assert maps.MAP_FLOPS_PER_D * 64 == 1280
    assert maps.layer_flops(1, 8192, 40, 64) == 20 * 2 * 1280 * full_pairs
    assert maps.layer_flops(1, 8192, 40, 64, 512) == \
        20 * 2 * 1280 * band_pairs
    # bytes as the band metric counts them: 0.46 ms a layer, under the
    # 1.06 ms of FLOPs even in the banded layer
    assert maps._band().layer_bytes(1, 8192, 40, 20, 64) == \
        8192 * 64 * 2 * (6 * 40 + 6 * 20)
    floor = maps.floor_seconds(family.kinds(cell.config), cell.config,
                               cell.traffic, peaks)
    by_hand = 51_200 * (2 * full_pairs + band_pairs) / 197e12
    assert floor == pytest.approx(by_hand, rel=1e-12)   # compute-bound
    assert floor == pytest.approx(0.0185, rel=2e-3)     # 18.5 ms a step
    # the four calls a layer run 4 x 7 x 2 x 64 x 20 = 71,680 a pair:
    # 1.4 times the mathematics
    assert 4 * 7 * 2 * 64 * 20 / 51_200 == 1.4


# ---------------------------------------------------------------------------
# the five metrics on the decoder tests' hand-built capture, renamed
# ---------------------------------------------------------------------------

HLO = (decoder_tests.HLO.replace("moe_route", "sel_scan")
       .replace("moe_combine", "gmu")
       .replace("moe_dispatch", "ssm_conv")
       .replace("moe_experts", "attn_diff"))
KERNEL = ("%flash_fwd_bhsd.8 = (bf16[20,8192,64]{2,1,0}, "
          "f32[20,8192,1]{2,1,0}) custom-call(%p.1), "
          "custom_call_target=\"tpu_custom_call\"")


def _context(hlo_text, cell=CELL, kernel=True):
    cell = spec.load_cell(cell)
    context = layers.Context(
        cell=cell, family=spec.load_module("families", cell.family),
        peaks=spec.load_peaks("TPU v5 lite"),
        system=types.SimpleNamespace(hlo_text=hlo_text, steps_per_call=1,
                                     state=(None, {}, None)),
        capture=decoder_tests._capture(), window_span="bench_window",
        traced_steps=2, items_per_s_per_chip=15_000.0)
    if kernel:
        context.capture.devices[0].lines["XLA Ops"].append(
            (KERNEL, 500, 600))
    return context


def test_the_new_metrics_read_the_hand_built_capture():
    """40 ns under ``sel_scan``, 200 under ``attn_diff``, 60 under
    ``gmu``, 100 in a flash kernel, over two traced steps."""
    context = _context(HLO)
    read = lambda name: spec.load_module(  # noqa: E731
        "metrics", name).read(context)
    assert read("sel_scan_ms_per_step") == pytest.approx(40e-6 / 2)
    assert read("attn_diff_ms_per_step") == pytest.approx(200e-6 / 2)
    assert read("gmu_ms_per_step") == pytest.approx(60e-6 / 2)
    assert read("sel_scan_roofline") == pytest.approx(
        100 * 2 * 2 * 8192 * 41_056 / 819e9 / 20e-9)
    assert read("diff_attn_roofline") == pytest.approx(
        100 * 51_200 * (2 * 33_558_528 + 4_063_488) / 197e12 / 50e-9)
    # the accepted metrics that list the cell read it unchanged
    assert read("flash_ms_per_step") == pytest.approx(100e-6 / 2)
    assert read("mfu") == pytest.approx(
        100 * 3 * 1.5270e9 * 15_000 / 197e12, rel=1e-4)
    # Without the program's names each reads None: on the parent's
    # program, where the scopes are not; and the two rooflines on another
    # family's cell, whose configuration has no such layer.
    bare = _context(decoder_tests.HLO.replace("moe_", "m_"), kernel=False)
    for name in NEW:
        assert spec.load_module("metrics", name).read(bare) is None, name
    other = _context(HLO, "nemotron3_super_ep64_s8192")
    for name in ("sel_scan_roofline", "diff_attn_roofline"):
        assert spec.load_module("metrics", name).read(other) is None, name


def test_the_new_cell_resolves_to_its_files_and_its_metrics_by_name():
    """By name: neither the number of cells nor the place of an entry in
    its list is held, so a later cell or metric leaves this test alone."""
    cell = spec.load_cell(CELL)
    assert (cell.chips, cell.family) == (1, "sambay_lm")
    assert cell.config_name == CONFIG
    assert cell.traffic_name == "seq8192_bs1_flash_remat"
    assert cell.traffic == spec.load_cell("laguna_s_ep32_s8192").traffic
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {"lm_head_ms_per_step", "flash_fwd_ms_per_step",
                       "flash_dq_ms_per_step", "flash_dkv_ms_per_step",
                       "flash_ms_per_step", "mfu",
                       "device_idle_share", "hbm_gb_per_step"} <= names
    # counted at another model's shapes, or (``ssm_ms_per_step``) the
    # other state-space mixer with its scan where this cell would read the
    # convolution alone: not this cell's
    assert not {"flash_roofline", "attn_band_roofline", "ssm_scan_roofline",
                "ssm_scan_ms_per_step", "ssm_ms_per_step", "moe_ms_per_step",
                "moe_experts_roofline", "latent_experts_roofline",
                "expert_tokens_per_step"} & names
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "throughput_per_chip"
        assert by_name[name]["source"] == "device_trace"
    assert {by_name[n]["layer"] for n in NEW} == {"kernels", "models"}
    workload = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (workload["config"], workload["traffic"], workload["chips"]) == (
        CONFIG, "seq8192_bs1_flash_remat", 1)
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["file"] == CONFIG_FILE
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/microsoft/"
                               "Phi-4-mini-flash-reasoning/blob/main/"
                               "config.json")
    # one four-chip cell in the benchmark, and it is not this one
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        "bert_base_s512_x4"]
    assert family.kinds(cell.config) == "MSMFGX"
    # every metric file of the cell is there and has a reader
    for m in cell.per_layer:
        assert callable(spec.load_module("metrics", m["name"]).read)


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's config, unchanged but the two counts
    that ``reduced`` lists, whose published values stand beside them; no
    width is touched; the cut passes the harness's rule."""
    with open(os.path.join(REPO, CONFIG_FILE)) as f:
        config = json.load(f)
    reduced = {"num_hidden_layers": (32, 6), "vocab_size": (200064, 25008)}
    assert config["reduced"] == list(reduced)
    assert config["published"] == {k: v[0] for k, v in reduced.items()}
    assert {k: config[k] for k in reduced} == {
        k: v[1] for k, v in reduced.items()}
    assert not [k for k in reduced if spec.is_width(k)]
    spec.check_cuts(config, CONFIG_FILE)
    assert 8 * config["vocab_size"] == 200064
    published = dict(
        embd_pdrop=0, hidden_act="silu", hidden_size=2560,
        intermediate_size=10240, layer_norm_eps=1e-5,
        max_position_embeddings=262144, mb_per_layer=2,
        model_type="phi4flash", num_attention_heads=40,
        num_key_value_heads=20, resid_pdrop=0, sliding_window=512,
        tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False)
    for key, value in published.items():
        assert config[key] == value, key
    assert config["layers_held"] == [0, 1, 16, 17, 18, 19]
    assert config["vocab_first_row"] == 0
    assert (config["mamba_d_state"], config["mamba_d_conv"],
            config["mamba_expand"], config["mamba_dt_rank"]) == (
        16, 4, 2, 160)
    assert config["mamba_dt_rank"] == -(-2560 // 16)
    assert config["departs"] == []
    assert len(config["assumed"]) >= 10
    for key in ("mamba_sizes", "head_pairing", "lambda", "memory",
                "scan_chunk", "ssm_initialisation", "attention_biases"):
        assert key in config["assumed"], key
    assert "groups of 8 chips" in config["deployment"]
    assert "26 layers left out" in config["deployment"]
    assert "2 of 6 here against 8 of 32" in config["deployment"]
    # 3e-5, not the other cells' 1e-4, at which this model's first layers
    # overshoot and the third loss multiplies the rounding (``assumed``)
    assert config["optimizer"] == {"name": "adamw", "learning_rate": 3e-5,
                                   "weight_decay": 0.01}
    assert "3e-5" in config["assumed"]["optimizer"]
    # the loss's limit lies under the float8 control's reading
    assert 0.0122 < config["loss_tolerance"]["abs"] < 0.08
    assert (config["compute_dtype"], config["param_dtype"]) == (
        "bfloat16", "float32")
    for tolerance in ("loss_tolerance", "update_tolerance"):
        why = config[tolerance]["why"]
        assert "fp8_reference" in why and "no_carry" in why \
            and "no_lambda" in why, tolerance
    assert config["update_tolerance"]["rel"] is None


def test_the_parameters_held_are_697_094_272_from_the_models_own_shapes():
    cell = spec.load_cell(CELL)
    model = family.make_model(cell.config, cell.traffic)
    shapes, extra = jax.eval_shape(
        lambda k: family.init_variables(model, k, cell.config,
                                        cell.traffic),
        jax.random.PRNGKey(0))
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    held = cell.config["parameters_held"]
    mlp = lambda layer: count([  # noqa: E731
        layer[k] for k in ("mlp_gate", "mlp_up", "mlp_down")])
    norms = lambda layer: count([  # noqa: E731
        layer["mixer_norm"], layer["mlp_norm"]])
    for name, layer in shapes.items():
        if name.startswith("layer_"):
            assert mlp(layer) == held["mlp_a_layer"] == 78_643_200
            assert norms(layer) == held["layer_norms_a_layer"] == 10_240
    mixer = lambda l: count(shapes[f"layer_{l}"]["mixer"])  # noqa: E731
    assert mixer(0) == mixer(16) == held["mixer_M"] == 41_241_600
    assert mixer(1) == mixer(17) == held["mixer_S_or_F"] == 19_668_864
    assert mixer(18) == held["mixer_G"] == 26_214_400
    assert mixer(19) == held["mixer_X"] == 13_112_704
    by_layer = {0: "layer_0_M", 1: "layer_1_S", 16: "layer_16_M",
                17: "layer_17_F", 18: "layer_18_G", 19: "layer_19_X"}
    for l, key in by_layer.items():
        assert count(shapes[f"layer_{l}"]) == held[key], key
    assert sum(held[key] for key in by_layer.values()) == held["layers"] \
        == 633_068_672
    assert count([shapes["tok_embed"], shapes["final_norm"]]) == \
        held["embedding_and_final_norm"] == 64_025_600
    assert "lm_head" not in shapes      # tied: the embedding is the head
    assert count(shapes) == held["total"] == 697_094_272
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(shapes))
    assert extra == {}


def test_the_reference_imports_nothing_of_the_system():
    with open(os.path.join(REPO, "benchmark/reference/sambay_lm.py")) as f:
        source = f.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import math",
                       "import jax", "import jax.numpy as jnp"]
    assert "horovod_tpu" not in source.split('"""', 2)[2]


# ---------------------------------------------------------------------------
# the planted controls do what their names say (``tiny_sambay_cell.plant``)
# ---------------------------------------------------------------------------

def test_no_lambda_leaves_the_first_map_alone_in_the_norm():
    """``lambda_zero`` makes every differential layer's lambda 0 to
    rounding, the model then computes ``rms(a1) (1 - lambda_init)`` (the
    reference's value at the same leaves) and no longer what it computed
    from the seeded leaves; no other leaf is touched."""
    reference = spec.load_module("reference", "sambay_lm")
    from horovod_tpu.models.sambay import lambda_init

    model = family.make_model(TINY, TRAFFIC)
    key = jax.random.PRNGKey(4)
    params, _ = family.init_variables(model, key, TINY, TRAFFIC)
    planted = tiny_sambay_cell.lambda_zero(params)
    changed = []
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree.leaves(planted)):
        if not np.array_equal(a, b):
            changed.append(jax.tree_util.keystr(path))
    assert changed == [f"['layer_{l}']['mixer']['lambda_{v}']"
                       for l in (1, 17, 19) for v in ("k2", "q1", "q2")]
    for l in (1, 17, 19):
        p = planted[f"layer_{l}"]["mixer"]
        lam = (jnp.exp(p["lambda_q1"] @ p["lambda_k1"])
               - jnp.exp(p["lambda_q2"] @ p["lambda_k2"]) + lambda_init(l))
        assert abs(float(lam)) < 1e-6
    tokens = family.make_batch(key, 1, TINY, TRAFFIC)[0]
    with jax.default_matmul_precision("highest"):
        sound = model.apply({"params": params}, tokens)[0]
        got = model.apply({"params": planted}, tokens)[0]
        want = reference.logits(planted, tokens[0], TINY)
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert float(jnp.abs(got - sound).max()) > 0.05


def test_no_carry_starts_every_chunk_from_a_zero_state(monkeypatch):
    """``plant("no_carry")`` reaches the scan the model calls: its output
    is the recurrence restarted at every chunk, in the later chunks no
    longer the whole recurrence's."""
    from horovod_tpu.ops import selective_scan as module

    monkeypatch.setattr(module, "_carry", module._carry)  # put back after
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    t, channels, states, chunk = 16, 6, 4, 4
    x, b, c = (jax.random.normal(k, (1, t, n)) for k, n in
               zip(ks[:3], (channels, states, states)))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (1, t, channels)) - 2)
    a = -jnp.exp(jax.random.uniform(ks[4], (channels, states), maxval=2.0))
    d = jax.random.normal(ks[5], (channels,))
    whole = module.selective_scan(x, dt, a, b, c, d, chunk=chunk)
    tiny_sambay_cell.plant("no_carry")
    cut = module.selective_scan(x, dt, a, b, c, d, chunk=chunk)
    restarted = jnp.concatenate([
        module.selective_scan(x[:, i:i + chunk], dt[:, i:i + chunk], a,
                              b[:, i:i + chunk], c[:, i:i + chunk], d,
                              chunk=chunk)
        for i in range(0, t, chunk)], axis=1)
    np.testing.assert_allclose(cut, restarted, atol=2e-5)
    np.testing.assert_array_equal(cut[:, :chunk], whole[:, :chunk])
    assert float(jnp.abs(cut[:, chunk:] - whole[:, chunk:]).max()) > 1e-2
