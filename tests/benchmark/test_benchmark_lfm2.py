"""The ``lfm2_lm`` family's FLOPs against the jaxpr's matmuls at a tiny
size and against a count by hand at the cell's, the short convolution's
roofline count by hand, the two new metrics on a hand-built capture, the
new cell's files and metrics by name, the configuration (every published
number, the cut), the reference's imports, and two planted controls of
``tiny_lfm2_cell.py`` doing what their names say."""

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
import tiny_lfm2_cell  # noqa: E402
from test_benchmark_flops import _matmul_flops  # noqa: E402

CELL = "lfm2_8b_a1b_ep4_s8192x2"
CONFIG = "lfm2_8b_a1b_ep4"
CONFIG_FILE = "benchmark/configs/lfm2_8b_a1b_ep4.json"
TRAFFIC_NAME = "seq8192_bs2_flash_remat"
family = spec.load_module("families", "lfm2_lm")

TINY = dict(tiny_lfm2_cell.LFM2, compute_dtype="float32")
TRAFFIC = dict(seq_len=32, attention="flash", remat=False)
NEW = ("short_conv_ms_per_step", "short_conv_roofline")
LISTED = ("lm_head_ms_per_step", "flash_fwd_ms_per_step",
          "flash_dq_ms_per_step", "flash_dkv_ms_per_step", "moe_ms_per_step",
          "moe_experts_ms_per_step", "expert_tokens_per_step",
          "expert_load_max_over_mean", "moe_experts_roofline")


def test_forward_flops_equal_the_jaxprs_matmuls_and_the_hand_counts(
        monkeypatch):
    """The jaxpr shows every product but two kinds whole: the attention
    (here a stand-in without products; by hand, visible pairs x 2 products
    x 2 x head size a query head) and the routed experts (a loop whose
    body the jaxpr holds once: one block of rows through three products;
    by hand, the expected top-k x held / all assignments a token). The
    convolution's taps multiply no matrix; the tied head's product is in
    it once."""
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setattr(
        fa, "flash_attention",
        lambda q, k, v, causal, window: jnp.zeros_like(q))
    model = family.make_model(TINY, TRAFFIC)
    key = jax.random.PRNGKey(0)
    params, extra = family.init_variables(model, key, TINY, TRAFFIC)
    samples, s = 2, TRAFFIC["seq_len"]
    batch = family.make_batch(key, samples, TINY, TRAFFIC)
    counted = _matmul_flops(jax.make_jaxpr(
        lambda p: family.loss_fn(model, p, extra, batch)[0])(params).jaxpr)
    assert family.kinds(TINY) == "CACCC"
    h, d, f, block_rows = 32, 8, 16, 256
    loop_body_once = 4 * (3 * 2.0 * block_rows * h * f)  # four sparse layers
    attention = samples * 4 * d * 4 * s * (s + 1) / 2    # one layer, 4 heads
    routed = samples * s * 4 * (4 * 4 / 16) * (3 * 2 * h * f)
    ours = family.forward_flops_per_item(TINY, TRAFFIC) * samples * s
    assert ours - attention - routed == counted - loop_body_once
    assert family.model_flops_per_item(TINY, TRAFFIC) == 3 * \
        family.forward_flops_per_item(TINY, TRAFFIC)


def test_the_cell_is_432_541_696_flop_a_token_forward():
    cell = spec.load_cell(CELL)
    forward = family.forward_flops_per_item(cell.config, cell.traffic)
    conv = 2 * 2048 * 6144 + 2 * 2048 * 2048
    attention = 2 * 2 * 2048 * 2048 + 2 * 2 * 2048 * 512
    scores = 4 * 64 * 32 * 8193 / 2
    router = 2 * 2048 * 32
    routed = 1 * 3 * 2 * 2048 * 1792       # top-4 x 8 held / 32
    dense = 3 * 2 * 2048 * 7168
    head = 2 * 2048 * 16384
    assert (conv, attention, router, routed, dense, head) == (
        33_554_432, 20_971_520, 131_072, 22_020_096, 88_080_384, 67_108_864)
    assert forward == (4 * conv + attention + scores + 4 * (router + routed)
                       + dense + head) == 432_541_696
    # 21.3 TFLOP a step of 2 x 8,192 tokens, forward and backward
    assert family.model_flops_per_item(cell.config, cell.traffic) * 16384 \
        == pytest.approx(21.26e12, rel=1e-3)
    # by shapes: the four conv operators' projections 31%, the head 16%
    assert 4 * conv / forward == pytest.approx(0.310, abs=2e-3)
    assert head / forward == pytest.approx(0.155, abs=2e-3)


def test_the_short_conv_roofline_floor_is_3_60_ms_at_the_cells_shape():
    cell = spec.load_cell(CELL)
    peaks = spec.load_peaks("TPU v5 lite")
    conv = spec.load_module("metrics", "short_conv_roofline")
    tokens = 2 * 8192
    # 3 passes x (two products + 2 x 3 taps) x 2,048 channels a token
    assert conv.conv_flops(tokens, 2048, 3) == 3 * tokens * 8 * 2048
    # B, C, x and y forward; dy, B, C, x and their gradients backward
    assert conv.conv_bytes(tokens, 2048) == tokens * 45_056
    floor = conv.floor_seconds(4, cell.config, cell.traffic, peaks)
    assert floor == pytest.approx(4 * tokens * 45_056 / 819e9, rel=1e-12)
    assert floor == pytest.approx(3.60e-3, rel=2e-3)       # bytes-bound
    assert 3 * tokens * 8 * 2048 / 197e12 < floor / 4 / 100


# ---------------------------------------------------------------------------
# the two metrics on the decoder tests' hand-built capture, renamed
# ---------------------------------------------------------------------------

HLO = decoder_tests.HLO.replace("moe_route", "short_conv")


def _context(hlo_text, cell=CELL):
    cell = spec.load_cell(cell)
    return layers.Context(
        cell=cell, family=spec.load_module("families", cell.family),
        peaks=spec.load_peaks("TPU v5 lite"),
        system=types.SimpleNamespace(hlo_text=hlo_text, steps_per_call=1,
                                     state=(None, {}, None)),
        capture=decoder_tests._capture(), window_span="bench_window",
        traced_steps=2, items_per_s_per_chip=50_000.0)


def test_the_new_metrics_read_the_hand_built_capture():
    """40 ns under ``short_conv`` over two traced steps."""
    context = _context(HLO)
    read = lambda name: spec.load_module(  # noqa: E731
        "metrics", name).read(context)
    assert read("short_conv_ms_per_step") == pytest.approx(40e-6 / 2)
    assert read("short_conv_roofline") == pytest.approx(
        100 * 4 * 16384 * 45_056 / 819e9 / 20e-9)
    assert read("mfu") == pytest.approx(
        100 * 3 * 432_541_696 * 50_000 / 197e12)
    # Without the program's names each reads None: on the parent's
    # program, where the scope is not; and the roofline on another
    # family's cell, whose configuration has no such layer.
    bare = _context(decoder_tests.HLO)
    for name in NEW:
        assert spec.load_module("metrics", name).read(bare) is None, name
    other = _context(HLO, "laguna_s_ep32_s8192")
    assert spec.load_module("metrics", "short_conv_roofline").read(
        other) is None


def test_the_experts_roofline_counts_this_models_shapes():
    """``moe_experts_roofline`` reads the cell's own widths and counters:
    at a uniform router (2,048 assignments for each of 8 held experts in
    4 sparse layers) the floor is 4 x 9 x 2 x 16,384 x 2,048 x 1,792
    FLOPs over the bf16 peak, 21.98 ms a step, FLOP-bound."""
    import numpy as np

    roofline = spec.load_module("metrics", "moe_experts_roofline")
    kept = [np.full(8, 2048)] * 4
    peaks = spec.load_peaks("TPU v5 lite")
    floor = roofline.floor_seconds(kept, spec.load_cell(CELL).config, peaks)
    assert floor == pytest.approx(4 * 9 * 2 * 16384 * 2048 * 1792 / 197e12)
    assert floor == pytest.approx(21.98e-3, rel=1e-3)


def test_the_new_cell_resolves_to_its_files_and_its_metrics_by_name():
    """By name: neither the number of cells nor the place of an entry in
    its list is held, so a later cell or metric leaves this test alone."""
    cell = spec.load_cell(CELL)
    assert (cell.chips, cell.family) == (1, "lfm2_lm")
    assert cell.config_name == CONFIG
    assert cell.traffic_name == TRAFFIC_NAME
    one = spec.load_cell("laguna_s_ep32_s8192").traffic
    assert cell.traffic == dict(one, per_chip_batch=2)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | set(LISTED) | {"flash_ms_per_step", "mfu",
                                     "device_idle_share",
                                     "hbm_gb_per_step"} <= names
    # counted at another model's shapes, or another model's operator
    # (``flash_roofline`` counts every layer as attention)
    assert not {"flash_roofline", "attn_band_roofline",
                "latent_experts_roofline",
                "ssm_ms_per_step", "sel_scan_roofline",
                "diff_attn_roofline"} & names
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "throughput_per_chip"
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["layer"] == "models"
    assert by_name["short_conv_roofline"]["unit"] == "%"
    for name in LISTED:
        assert CELL in by_name[name]["workloads"], name
    workload = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (workload["config"], workload["traffic"], workload["chips"]) == (
        CONFIG, TRAFFIC_NAME, 1)
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["file"] == CONFIG_FILE
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/LiquidAI/LFM2-8B-A1B/"
                               "blob/main/config.json")
    assert family.kinds(cell.config) == "CACCC"
    # every metric file of the cell is there and has a reader
    for m in cell.per_layer:
        assert callable(spec.load_module("metrics", m["name"]).read)


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's config, unchanged but the three counts
    that ``reduced`` lists, whose published values stand beside them; no
    width is touched; the cut passes the harness's rule."""
    with open(os.path.join(REPO, CONFIG_FILE)) as f:
        config = json.load(f)
    reduced = {"num_hidden_layers": (24, 5), "num_experts": (32, 8),
               "vocab_size": (65536, 16384)}
    assert config["reduced"] == list(reduced)
    assert config["published"] == {k: v[0] for k, v in reduced.items()}
    assert {k: config[k] for k in reduced} == {
        k: v[1] for k, v in reduced.items()}
    assert not [k for k in reduced if spec.is_width(k)]
    spec.check_cuts(config, CONFIG_FILE)
    published = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=2048,
        intermediate_size=7168, max_position_embeddings=128000,
        model_type="lfm2_moe", moe_intermediate_size=1792, norm_eps=1e-5,
        norm_topk_prob=True, num_attention_heads=32, num_dense_layers=2,
        num_experts_per_tok=4, num_key_value_heads=8, rope_theta=1000000,
        routed_scaling_factor=1, use_expert_bias=True)
    for key, value in published.items():
        assert config[key] == value, key
    layer_types = config["layer_types"]
    assert len(layer_types) == 24
    assert [i for i, t in enumerate(layer_types) if t == "full_attention"] \
        == [2, 6, 10, 14, 18, 21]
    assert set(layer_types) == {"conv", "full_attention"}
    assert config["layers_held"] == [0, 2, 3, 4, 5]
    assert (config["first_expert"], config["vocab_first_row"]) == (0, 0)
    assert config["router_eps"] == 1e-6
    assert config["departs"] == []
    for key in ("tied_head", "head_dim", "in_proj_split", "expert_bias",
                "dense_width", "qk_norm", "initialisation",
                "auxiliary_loss"):
        assert key in config["assumed"], key
    assert "4 chips share each sparse layer" in config["deployment"]
    assert (config["compute_dtype"], config["param_dtype"]) == (
        "bfloat16", "float32")
    assert config["optimizer"]["name"] == "adamw"
    for tolerance in ("loss_tolerance", "update_tolerance"):
        why = config[tolerance]["why"]
        for control in tiny_lfm2_cell.CONTROLS:
            assert control in why, (tolerance, control)
    assert config["update_tolerance"]["rel"] is None


def test_the_reference_imports_nothing_of_the_system():
    with open(os.path.join(REPO, "benchmark/reference/lfm2_lm.py")) as f:
        source = f.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import math",
                       "import jax", "import jax.numpy as jnp"]
    assert "horovod_tpu" not in source.split('"""', 2)[2]


# ---------------------------------------------------------------------------
# the planted controls do what their names say (``tiny_lfm2_cell.plant``)
# ---------------------------------------------------------------------------

def test_no_conv_leaves_each_position_its_own_product_alone(monkeypatch):
    from horovod_tpu.models import decoder

    monkeypatch.setattr(decoder, "causal_conv", decoder.causal_conv)
    v = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 4))
    taps = jax.random.normal(jax.random.PRNGKey(1), (3, 4))
    whole = decoder.causal_conv(v, taps)
    tiny_lfm2_cell.plant("no_conv")
    cut = decoder.causal_conv(v, taps)
    np.testing.assert_allclose(cut, v * taps[-1], rtol=1e-6)
    np.testing.assert_array_equal(cut[:, 0], whole[:, 0])  # nothing before
    assert float(jnp.abs(cut[:, 1:] - whole[:, 1:]).min()) > 0


def test_no_qk_norm_scales_the_heads_and_does_not_norm_them(monkeypatch):
    from horovod_tpu.models import decoder

    monkeypatch.setattr(decoder, "head_rms", decoder.head_rms)
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(2), (1, 4, 2, 8))
    scale = jnp.full((8,), 0.5)
    tiny_lfm2_cell.plant("no_qk_norm")
    np.testing.assert_allclose(decoder.head_rms(x, scale, 1e-5), 0.5 * x,
                               rtol=1e-6)
