"""The ``hybrid_lm`` family's FLOPs against the jaxpr's matmuls at a tiny
size and against a count by hand at the cell's, the two new roofline
counts by hand, the five new metrics on a hand-built capture, the new
cell's files, and the configuration: every published number, the cut,
the parameter total from the model's own shapes."""

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
import tiny_hybrid_cell  # noqa: E402
from test_benchmark_flops import _matmul_flops  # noqa: E402

CELL = "nemotron3_super_ep64_s8192"
CONFIG_FILE = "benchmark/configs/nemotron3_super_ep64.json"
family = spec.load_module("families", "hybrid_lm")

TINY = dict(tiny_hybrid_cell.HYBRID, compute_dtype="float32")
TRAFFIC = dict(seq_len=32, attention="flash", remat=False)


def test_forward_flops_equal_the_jaxprs_matmuls_and_the_hand_counts(
        monkeypatch):
    """The jaxpr shows every product but two kinds whole: the attention
    (here a stand-in without products; by hand, visible pairs x 2 products
    x 2 x head size a query head) and the routed experts (a loop whose
    body the jaxpr holds once: one block of rows through two products;
    by hand, the expected top-k x held / all assignments a token). The
    scan's four products are in it as the chunked form makes them."""
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setattr(
        fa, "flash_attention",
        lambda q, k, v, causal, window: jnp.zeros_like(q))
    model = family.make_model(TINY, TRAFFIC)
    key = jax.random.PRNGKey(0)
    params, extra = family.init_variables(model, key, TINY, TRAFFIC)
    samples = 2
    batch = family.make_batch(key, samples, TINY, TRAFFIC)
    counted = _matmul_flops(jax.make_jaxpr(
        lambda p: family.loss_fn(model, p, extra, batch)[0])(params).jaxpr)
    tokens, s = samples * 32, 32
    pattern = family.pattern(TINY)
    assert pattern == "MEM*E"
    attention = 4 * 8 * 4 * (s * (s + 1) // 2) / s          # a token
    routed = 4 * 8 / 16 * 2 * 2 * 16 * 24                   # a token
    one_block = 2 * 2 * 256 * 16 * 24   # the loop's body, 256 rows, once
    by_hand = (counted - pattern.count("E") * one_block
               + tokens * (pattern.count("*") * attention
                           + pattern.count("E") * routed))
    assert family.forward_flops_per_item(TINY, TRAFFIC) * tokens == by_hand
    # the scan alone: scores, scores against x, end state, entering state
    assert family.scan_flops_per_item(TINY) == (
        2 * 16 * 8 * 2 + 2 * 16 * 8 * 4 + 4 * 8 * 8 * 4)


def test_the_cell_is_1_02_gflop_a_token_forward():
    cell = spec.load_cell(CELL)
    forward = family.forward_flops_per_item(cell.config, cell.traffic)
    state_space = (2 * 4096 * 4640 + 2 * 2048 * 4096
                   + 2 * 128 * 128 * 2 + 2 * 128 * 64 * 32
                   + 4 * 128 * 64 * 32)
    attention = (2 * 2 * 4096 * 8 * 128 + 2 * 2 * 4096 * 128
                 + 4 * 128 * 8 * 8193 / 2)
    experts = (2 * 4096 * 512 + 2 * 2 * 4096 * 1024 + 2 * 2 * 4096 * 5376
               + 22 * 8 / 512 * 2 * 2 * 1024 * 2688)
    head = 2 * 4096 * 16384
    assert (state_space, experts, head) == (56_426_496, 112_836_608.0,
                                            134_217_728)
    assert forward == 5 * state_space + attention + 5 * experts + head
    assert forward == pytest.approx(1.0162e9, rel=1e-4)
    # 25 TFLOP a step of 8,192 tokens, forward and backward
    assert family.model_flops_per_item(cell.config, cell.traffic) * 8192 \
        == pytest.approx(24.97e12, rel=1e-3)
    # by shapes: the ten blocks of the new kinds are 83% of it
    assert (5 * state_space + 5 * experts) / forward == pytest.approx(
        0.833, abs=2e-3)


def test_roofline_counts_by_hand_at_the_cells_shape():
    cell = spec.load_cell(CELL)
    peaks = spec.load_peaks("TPU v5 lite")
    scan = spec.load_module("metrics", "ssm_scan_roofline")
    assert scan.scan_flops(8192, 128, 128, 2, 64, 32) == \
        3 * 8192 * 1_638_400
    # x, B, C, dt twice; y, dy; dx, dB, dC, ddt: 11,872 elements a token
    assert scan.scan_bytes(8192, 128, 2, 64, 32) == 2 * 8192 * 11_872
    floor = scan.floor_seconds(cell.config, cell.traffic, peaks)
    # bytes-bound: 0.2375 ms of bytes against 0.2044 ms of FLOPs a block
    assert floor == pytest.approx(5 * 2 * 8192 * 11_872 / 819e9)
    assert floor / 5 == pytest.approx(0.2375e-3, rel=1e-3)
    assert 3 * 8192 * 1_638_400 / 197e12 == pytest.approx(0.2044e-3,
                                                           rel=1e-3)

    experts = spec.load_module("metrics", "latent_experts_roofline")
    assert experts.experts_flops(2816, 1024, 2688) == \
        3 * 2 * 2 * 2816 * 1024 * 2688
    assert experts.experts_bytes(8, 1024, 2688) == 2 * 8 * 1024 * 2688 * 2
    kept = np.full((5, 8), 352)
    # 0.472 ms of FLOPs against 0.108 ms of bytes a block
    assert experts.floor_seconds(kept, cell.config, peaks) == pytest.approx(
        5 * 3 * 2 * 2 * 2816 * 1024 * 2688 / 197e12)
    # an idle block still reads its weights once
    assert experts.floor_seconds(np.zeros((1, 8), int), cell.config,
                                 peaks) == pytest.approx(88_080_384 / 819e9)


# ---------------------------------------------------------------------------
# the five metrics on the decoder tests' hand-built capture, renamed
# ---------------------------------------------------------------------------

HLO = (decoder_tests.HLO.replace("moe_route", "ssm_scan")
       .replace("moe_combine", "moe_latent")
       .replace("moe_dispatch", "ssm_conv"))


def _context(hlo_text, extra=None):
    return layers.Context(
        cell=spec.load_cell(CELL), family=family,
        peaks=spec.load_peaks("TPU v5 lite"),
        system=types.SimpleNamespace(hlo_text=hlo_text, steps_per_call=1,
                                     state=(None, extra, None)),
        capture=decoder_tests._capture(), window_span="bench_window",
        traced_steps=2, items_per_s_per_chip=20_000.0)


def test_the_new_metrics_read_the_hand_built_capture():
    """40 ns under ``ssm_scan``, 200 under ``moe_experts``, 60 under
    ``moe_latent``, over two traced steps."""
    kept = np.array([[330, 370, 352, 352, 340, 364, 352, 356]] * 5)
    extra = {"expert_kept": jnp.asarray(kept, jnp.int32),
             "expert_elsewhere": jnp.asarray([8192 * 22 - 2816] * 5,
                                             jnp.int32)}
    context = _context(HLO, extra)
    read = lambda name: spec.load_module(  # noqa: E731
        "metrics", name).read(context)
    assert read("ssm_ms_per_step") == pytest.approx(40e-6 / 2)
    assert read("ssm_scan_ms_per_step") == pytest.approx(40e-6 / 2)
    assert read("moe_latent_ms_per_step") == pytest.approx(60e-6 / 2)
    assert read("ssm_scan_roofline") == pytest.approx(
        100 * 5 * 2 * 8192 * 11_872 / 819e9 / 20e-9)
    assert read("latent_experts_roofline") == pytest.approx(
        100 * 5 * 3 * 2 * 2 * 2816 * 1024 * 2688 / 197e12 / 100e-9)
    # the accepted metrics that list the cell read it unchanged
    assert read("moe_experts_ms_per_step") == pytest.approx(200e-6 / 2)
    assert read("moe_ms_per_step") == pytest.approx(200e-6 / 2)
    assert read("expert_tokens_per_step") == 5 * 2816.0
    assert read("expert_load_max_over_mean") == pytest.approx(370 / 352)
    assert read("mfu") == pytest.approx(
        100 * 3 * 1.0162e9 * 20_000 / 197e12, rel=1e-4)
    # Without the program's names or counters each reads None: on the
    # parent's program, and on another family's cell.
    bare = _context(decoder_tests.HLO.replace("moe_", "m_"), {})
    other = decoder_tests._context(HLO, extra)
    for name in ("ssm_ms_per_step", "ssm_scan_ms_per_step",
                 "ssm_scan_roofline", "moe_latent_ms_per_step",
                 "latent_experts_roofline"):
        assert spec.load_module("metrics", name).read(bare) is None, name
    for name in ("ssm_scan_roofline", "latent_experts_roofline"):
        assert spec.load_module("metrics", name).read(other) is None, name


def test_the_new_cell_resolves_to_its_files_and_its_metrics():
    cell = spec.load_cell(CELL)
    assert (cell.chips, cell.family) == (1, "hybrid_lm")
    assert cell.config_name == "nemotron3_super_ep64"
    assert cell.traffic_name == "seq8192_bs1_flash_remat"
    assert cell.traffic == spec.load_cell("laguna_s_ep32_s8192").traffic
    names = {m["name"] for m in cell.per_layer}
    new = {"ssm_ms_per_step", "ssm_scan_ms_per_step", "ssm_scan_roofline",
           "moe_latent_ms_per_step", "latent_experts_roofline"}
    assert new | {"lm_head_ms_per_step", "flash_fwd_ms_per_step",
                  "flash_dkv_ms_per_step", "moe_ms_per_step",
                  "moe_experts_ms_per_step", "expert_tokens_per_step",
                  "expert_load_max_over_mean", "flash_ms_per_step",
                  "mfu"} <= names
    # counted at another model's shapes: not this cell's
    assert not {"moe_experts_roofline", "attn_band_roofline",
                "flash_roofline"} & names
    bench = spec.load_benchmark()
    for m in bench["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "throughput_per_chip"
    assert [m["name"] for m in bench["per_layer"][-5:]] == [
        "ssm_ms_per_step", "ssm_scan_ms_per_step", "ssm_scan_roofline",
        "moe_latent_ms_per_step", "latent_experts_roofline"]
    assert bench["workloads"][-1]["name"] == CELL
    assert len(bench["workloads"]) == 6
    assert family.pattern(cell.config) == "MEMEMEM*EME"
    assert len(cell.config["hybrid_override_pattern"]) == 88  # kept whole


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's config, unchanged but the seven counts
    that ``reduced`` lists, whose published values stand beside them; no
    width is touched; the cut passes the harness's rule."""
    with open(os.path.join(REPO, CONFIG_FILE)) as f:
        config = json.load(f)
    reduced = {"num_hidden_layers": (88, 11), "n_routed_experts": (512, 8),
               "vocab_size": (131072, 16384), "mamba_num_heads": (128, 32),
               "n_groups": (8, 2), "num_attention_heads": (32, 8),
               "num_key_value_heads": (2, 1)}
    assert config["reduced"] == list(reduced)
    assert config["published"] == {k: v[0] for k, v in reduced.items()}
    assert {k: config[k] for k in reduced} == {
        k: v[1] for k, v in reduced.items()}
    assert not [k for k in reduced if spec.is_width(k)]
    spec.check_cuts(config, CONFIG_FILE)
    published = dict(
        hidden_size=4096, mamba_head_dim=64, ssm_state_size=128,
        conv_kernel=4, chunk_size=128, head_dim=128, moe_latent_size=1024,
        moe_intermediate_size=2688, intermediate_size=2688,
        moe_shared_expert_intermediate_size=5376, num_experts_per_tok=22,
        expand=2, n_group=1, topk_group=1, routed_scaling_factor=5,
        norm_topk_prob=True, layer_norm_epsilon=1e-5, norm_eps=1e-5,
        n_shared_experts=1, mlp_hidden_act="relu2", mamba_hidden_act="silu",
        use_conv_bias=True, use_bias=False, mamba_proj_bias=False,
        attention_bias=False, mlp_bias=False, tie_word_embeddings=False,
        time_step_min=0.001, time_step_max=0.1, time_step_floor=0.0001,
        rope_theta=10000, partial_rotary_factor=1, sliding_window=None,
        max_position_embeddings=262144, num_nextn_predict_layers=1,
        mtp_hybrid_override_pattern="*E", rescale_prenorm_residual=True,
        residual_in_fp32=False, model_type="nemotron_h",
        moe_shared_expert_overlap=False, num_logits_to_keep=1,
        use_mamba_kernels=True)
    for key, value in published.items():
        assert config[key] == value, key
    pattern = config["hybrid_override_pattern"]
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (
        40, 40, 8)
    held = pattern[:11]
    assert (held.count("M"), held.count("E"), held.count("*")) == (5, 5, 1)
    assert len(config["departs"]) == 1 and "multi-token" in config[
        "departs"][0]
    assert len(config["assumed"]) >= 7
    assert "64 chips" in config["deployment"]
    assert "4 chips" in config["deployment"]
    assert config["optimizer"] == {"name": "adamw", "learning_rate": 1e-4,
                                   "weight_decay": 0.01}
    assert (config["compute_dtype"], config["param_dtype"]) == (
        "bfloat16", "float32")


def test_the_parameters_held_are_773_582_304_from_the_models_own_shapes():
    cell = spec.load_cell(CELL)
    model = family.make_model(cell.config, cell.traffic)
    shapes, extra = jax.eval_shape(
        lambda k: family.init_variables(model, k, cell.config,
                                        cell.traffic),
        jax.random.PRNGKey(0))
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    held = cell.config["parameters_held"]
    assert count(shapes["block_0"]) == held["state_space_block_32_heads"] \
        == 27_413_088
    assert count(shapes["block_7"]) == held["attention_block_8_heads"] \
        == 9_441_280
    assert count(shapes["block_1"]) == held["expert_block"] == 98_570_752
    assert count([shapes["tok_embed"], shapes["lm_head"],
                  shapes["final_norm"]]) == \
        held["embedding_head_and_final_norm"] == 134_221_824
    assert count(shapes) == held["total"] == 773_582_304
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(shapes))
    # the counters the routing metrics read: five expert blocks of eight
    assert extra["expert_kept"].shape == (5, 8)
    assert extra["expert_elsewhere"].shape == (5,)
