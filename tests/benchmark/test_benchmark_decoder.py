"""The ``decoder_lm`` family's FLOPs against the jaxpr's matmuls at a
tiny size, the two roofline counts by hand at the cell's shape, the scope
reader (``benchmark/harness/scopes.py``) on a hand-built capture, and the
new cell's files."""

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

from benchmark.harness import layers, scopes, spec, xtrace  # noqa: E402
from test_benchmark_flops import _matmul_flops  # noqa: E402

CELL = "laguna_s_ep32_s8192"
family = spec.load_module("families", "decoder_lm")

TINY = dict(
    hidden_size=64, head_dim=16, num_key_value_heads=2, num_hidden_layers=3,
    vocab_size=96, intermediate_size=128,
    layer_types=["full_attention", "sliding_attention", "full_attention"],
    num_attention_heads_per_layer=[4, 6, 4],
    mlp_layer_types=["dense", "sparse", "sparse"],
    sliding_window=8, rms_norm_eps=1e-6,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    num_experts=4, published={"num_experts": 16}, first_expert=0,
    num_experts_per_tok=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, moe_routed_scaling_factor=2.5,
    compute_dtype="float32")
TRAFFIC = dict(seq_len=32, attention="flash", remat=False)


def test_forward_flops_equal_the_jaxprs_matmuls_and_the_hand_counts(
        monkeypatch):
    """The jaxpr shows every product but two kinds whole: the attention
    (here a stand-in without products; by hand, visible pairs x 2 products
    x 2 x head size a query head) and the routed experts (a loop whose
    body the jaxpr holds once: one block of rows through three products;
    by hand, the expected top-k x held / all assignments a token)."""
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setattr(
        fa, "flash_attention",
        lambda q, k, v, causal, window: jnp.zeros_like(q))
    model = family.make_model(TINY, TRAFFIC)
    block_rows = 256  # expert_share_layer's
    key = jax.random.PRNGKey(0)
    params, extra = family.init_variables(model, key, TINY, TRAFFIC)
    samples, s = 2, TRAFFIC["seq_len"]
    batch = family.make_batch(key, samples, TINY, TRAFFIC)
    counted = _matmul_flops(jax.make_jaxpr(
        lambda p: family.loss_fn(model, p, extra, batch)[0])(params).jaxpr)

    h, d, f = 64, 16, 32
    loop_body_once = 2 * (3 * 2.0 * block_rows * h * f)  # two sparse layers
    pairs = {"full": s * (s + 1) // 2,
             "window": sum(min(i + 1, 8) for i in range(s))}
    attention = samples * sum(
        4 * d * heads * pairs[kind] for kind, heads in
        (("full", 4), ("window", 6), ("full", 4)))
    routed = samples * s * 2 * (4 * 4 / 16) * (3 * 2 * h * f)
    ours = family.forward_flops_per_item(TINY, TRAFFIC) * samples * s
    assert ours - attention - routed == counted - loop_body_once
    assert family.model_flops_per_item(TINY, TRAFFIC) == 3 * \
        family.forward_flops_per_item(TINY, TRAFFIC)
    assert family.visible_pairs(s, 8) == pairs["window"]
    assert family.visible_pairs(s) == family.visible_pairs(s, s) == \
        pairs["full"]


def test_the_cell_is_1_22_gflop_a_token_forward():
    cell = spec.load_cell(CELL)
    forward = family.forward_flops_per_item(cell.config, cell.traffic)
    assert forward == pytest.approx(1.2207e9, rel=1e-4)
    # 30 TFLOP a step of 8,192 tokens, forward and backward
    assert 3 * forward * 8192 == pytest.approx(30.0e12, rel=1e-3)


def test_roofline_counts_by_hand_at_the_cells_shape():
    band = spec.load_module("metrics", "attn_band_roofline")
    s, d = 8192, 128
    full_pairs = 8192 * 8193 // 2
    band_pairs = 512 * 513 // 2 + (8192 - 512) * 512
    assert (full_pairs, band_pairs) == (33_558_528, 4_063_488)
    assert band.visible_pairs(s) == full_pairs
    assert band.visible_pairs(s, 512) == band_pairs
    assert band.layer_flops(1, s, 48, d) == 7 * 2 * 48 * full_pairs * d
    assert band.layer_flops(1, s, 72, d, 512) == 7 * 2 * 72 * band_pairs * d
    # q, o (twice), do, dq at 72 heads; k, v (twice), dk, dv at 8
    assert band.layer_bytes(1, s, 72, 8, d) == s * d * 2 * (6 * 72 + 6 * 8)
    cell = spec.load_cell(CELL)
    peaks = spec.load_peaks("TPU v5 lite")
    floor = band.floor_seconds(cell.config, cell.traffic, peaks)
    by_hand = (2 * 7 * 2 * 48 * full_pairs * d
               + 3 * 7 * 2 * 72 * band_pairs * d) / 197e12
    assert floor == pytest.approx(by_hand, rel=1e-12)  # compute-bound
    assert floor == pytest.approx(0.03729, rel=1e-3)   # 37 ms a step

    experts = spec.load_module("metrics", "moe_experts_roofline")
    assert experts.experts_flops(2560, 3072, 1024) == 144_955_146_240
    assert experts.experts_bytes(8, 3072, 1024) == 150_994_944
    kept = np.full((4, 8), 320)
    # 0.736 ms of FLOPs against 0.184 ms of bytes a layer
    assert experts.floor_seconds(kept, cell.config, peaks) == pytest.approx(
        4 * 144_955_146_240 / 197e12)
    # an idle layer still reads its weights once
    assert experts.floor_seconds(np.zeros((1, 8), int), cell.config,
                                 peaks) == pytest.approx(150_994_944 / 819e9)


# ---------------------------------------------------------------------------
# the scope reader on a hand-built text and capture
# ---------------------------------------------------------------------------

F32 = "f32[8,8]{1,0}"
FWD = "jit(train_step)/shard_map/jvp(Decoder)/layer_1/moe"
BWD = "jit(train_step)/shard_map/transpose(jvp(Decoder))/layer_1/moe"


def _meta(op_name):
    return f'metadata={{op_name="{op_name}" stack_frame_id=3}}'


HLO = "\n".join([
    "HloModule jit_train_step, is_scheduled=true",
    "",
    # A gather fused with the product it feeds: the product's scope.
    f"%fused_computation.1 (param_0: {F32}, param_1: {F32}) -> {F32} {{",
    f"  %param_0 = {F32} parameter(0)",
    f"  %param_1 = {F32} parameter(1)",
    f"  %gather.1 = {F32} gather(%param_0, %param_1), "
    + _meta(f"{FWD}/while/body/moe_dispatch/gather"),
    f"  ROOT %dot.1 = {F32} dot(%gather.1, %param_1), "
    + _meta(f"{FWD}/while/body/moe_experts/dot_general"),
    "}",
    "",
    # No product and no name of its own: what most of it says.
    f"%fused_computation.2 (param_0.1: {F32}) -> {F32} {{",
    f"  %param_0.1 = {F32} parameter(0)",
    f"  %multiply.2 = {F32} multiply(%param_0.1, %param_0.1), "
    + _meta(f"{BWD}/while/body/moe_combine/mul"),
    f"  %add.2 = {F32} add(%multiply.2, %param_0.1), "
    + _meta(f"{BWD}/while/body/moe_combine/add"),
    f"  ROOT %convert.2 = {F32} convert(%add.2), "
    + _meta(f"{BWD}/while/body/moe_dispatch/convert"),
    "}",
    "",
    # The loop's body: its instructions are events of their own.
    f"%body.3 (arg: ({F32})) -> ({F32}) {{",
    f"  %arg = ({F32}) parameter(0)",
    f"  %gte.3 = {F32} get-tuple-element(%arg), index=0",
    f"  %fusion.1 = {F32} fusion(%gte.3, %gte.3), kind=kOutput, "
    "calls=%fused_computation.1, "
    + _meta(f"{FWD}/while/body/moe_experts/dot_general"),
    f"  %fusion.2 = {F32} fusion(%fusion.1), kind=kLoop, "
    "calls=%fused_computation.2",
    f"  ROOT %tuple.3 = ({F32}) tuple(%fusion.2)",
    "}",
    "",
    f"ENTRY %main.9 (p.1: {F32}) -> {F32} {{",
    f"  %p.1 = {F32} parameter(0)",
    f"  %sort.4 = {F32} sort(%p.1), dimensions={{0}}, "
    + _meta(f"{FWD}/moe_route/top_k"),
    f"  %tuple.5 = ({F32}) tuple(%sort.4)",
    f"  %while.6 = ({F32}) while(%tuple.5), condition=%cond, body=%body.3, "
    + _meta(f"{FWD}/while"),
    f"  %gte.7 = {F32} get-tuple-element(%while.6), index=0",
    f"  %logistic.8 = {F32} logistic(%gte.7), "
    + _meta("jit(train_step)/shard_map/jvp(Decoder)/layer_1/attention/"
            "attn_gate/logistic"),
    f"  ROOT %add.9 = {F32} add(%logistic.8, %p.1), "
    + _meta("jit(train_step)/shard_map/jvp(Decoder)/layer_1/add"),
    "}",
])

EVENTS = [  # (identifier, the rest of the event's name, start ns, end ns)
    ("sort.4", f"= {F32} sort({F32} %p.1), dimensions={{0}}", 0, 40),
    ("while.6", f"= ({F32}) while(%tuple.5), body=%body.3", 40, 400),
    ("fusion.1", f"= {F32} fusion({F32} %gte.3, {F32} %gte.3), "
     "kind=kOutput, calls=%fused_computation.1", 50, 150),
    ("fusion.2", f"= {F32} fusion({F32} %fusion.1), kind=kLoop, "
     "calls=%fused_computation.2", 150, 180),
    ("fusion.1", f"= {F32} fusion({F32} %gte.3, {F32} %gte.3), "
     "kind=kOutput, calls=%fused_computation.1", 200, 300),
    ("fusion.2", f"= {F32} fusion({F32} %fusion.1), kind=kLoop, "
     "calls=%fused_computation.2", 300, 330),
    ("logistic.8", f"= {F32} logistic({F32} %gte.7)", 400, 420),
    ("add.9", f"= {F32} add({F32} %logistic.8, {F32} %p.1)", 420, 500),
]
WINDOW = (0.0, 1000.0)
MOE = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine",
       "moe_shared")


def _capture():
    ops = [(f"%{key} {text}", s, e) for key, text, s, e in EVENTS]
    return xtrace.Capture(
        devices=[xtrace.DevicePlane("/device:TPU:0", {"XLA Ops": ops})],
        host=[("bench_window", *WINDOW)])


def _context(hlo_text, extra=None):
    return layers.Context(
        cell=spec.load_cell(CELL), family=family,
        peaks=spec.load_peaks("TPU v5 lite"),
        system=types.SimpleNamespace(hlo_text=hlo_text, steps_per_call=1,
                                     state=(None, extra, None)),
        capture=_capture(), window_span="bench_window", traced_steps=2,
        items_per_s_per_chip=1.0)


def test_scope_reader_joins_events_to_names_and_skips_the_wrapper():
    (seconds,) = scopes.read(HLO, _capture(), WINDOW, MOE)
    assert {k: round(v * 1e9) for k, v in seconds.items()} == {
        "moe_route": 40,      # by its own name
        "moe_experts": 200,   # a fusion goes where its product goes
        "moe_combine": 60}    # a nameless fusion: what most of it says
    # the while is a wrapper; the gate and the residual add are not moe
    (gate,) = scopes.read(HLO, _capture(), WINDOW, ("attn_gate",))
    assert {k: round(v * 1e9) for k, v in gate.items()} == {"attn_gate": 20}
    # a window that cuts an event counts the part inside
    (cut,) = scopes.read(HLO, _capture(), (100.0, 1000.0), ("moe_experts",))
    assert round(cut["moe_experts"] * 1e9) == 150
    # a program from before the names reads None, never 0
    bare = HLO.replace("moe_", "m_").replace("attn_gate", "g")
    assert scopes.read(bare, _capture(), WINDOW, MOE) is None


def test_the_six_metrics_read_the_hand_built_capture():
    kept = np.array([[300, 340, 320, 320, 310, 330, 320, 320]] * 4)
    extra = {"expert_kept": jnp.asarray(kept, jnp.int32),
             "expert_elsewhere": jnp.asarray([79360] * 4, jnp.int32)}
    context = _context(HLO, extra)
    read = lambda name: spec.load_module(  # noqa: E731
        "metrics", name).read(context)
    assert read("moe_ms_per_step") == pytest.approx(300e-6 / 2)
    assert read("moe_experts_ms_per_step") == pytest.approx(200e-6 / 2)
    assert read("expert_tokens_per_step") == 10240.0
    assert read("expert_load_max_over_mean") == pytest.approx(340 / 320)
    floor = 4 * 144_955_146_240 / 197e12
    assert read("moe_experts_roofline") == pytest.approx(
        100 * floor / (100e-9))
    assert read("attn_band_roofline") is None  # no flash kernel in it
    # Without the program's names or counters each reads None.
    bare = _context(HLO.replace("moe_", "m_"), {})
    for name in ("moe_ms_per_step", "moe_experts_ms_per_step",
                 "moe_experts_roofline", "expert_tokens_per_step",
                 "expert_load_max_over_mean", "attn_band_roofline"):
        assert spec.load_module("metrics", name).read(bare) is None, name


def test_attn_band_roofline_reads_the_kernels_events():
    kernel = ("%flash_fwd_bhsd.8 = (bf16[48,8192,128]{2,1,0}, "
              "f32[48,8192,1]{2,1,0}) custom-call(%p.1), "
              "custom_call_target=\"tpu_custom_call\"")
    context = _context(HLO)
    context.capture.devices[0].lines["XLA Ops"].append((kernel, 500, 600))
    got = spec.load_module("metrics", "attn_band_roofline").read(context)
    floor = spec.load_module("metrics", "attn_band_roofline").floor_seconds(
        context.cell.config, context.cell.traffic, context.peaks)
    assert got == pytest.approx(100 * floor / (100e-9 / 2))


def test_the_new_cell_resolves_to_its_files_and_its_metrics():
    cell = spec.load_cell(CELL)
    assert (cell.chips, cell.family) == (1, "decoder_lm")
    assert cell.traffic_name == "seq8192_bs1_flash_remat"
    assert (cell.traffic["per_chip_batch"], cell.traffic["seq_len"],
            cell.traffic["remat"]) == (1, 8192, True)
    names = {m["name"] for m in cell.per_layer}
    assert {"moe_ms_per_step", "moe_experts_ms_per_step",
            "moe_experts_roofline", "expert_tokens_per_step",
            "expert_load_max_over_mean", "attn_band_roofline",
            "flash_ms_per_step", "flash_fwd_ms_per_step",
            "flash_dq_ms_per_step", "flash_dkv_ms_per_step",
            "lm_head_ms_per_step", "mfu"} <= names
    # its head size is not hidden / heads: attn_band_roofline stands in
    assert "flash_roofline" not in names
    bench = spec.load_benchmark()
    for m in bench["per_layer"][-6:]:
        assert m["workloads"] == [CELL] and m["moves"] == \
            "throughput_per_chip"
    # The layers held: the published lists' first five entries.
    assert family.layer_kinds(cell.config) == [
        ("full", 48, "dense"), ("window", 72, "sparse"),
        ("window", 72, "sparse"), ("window", 72, "sparse"),
        ("full", 48, "sparse")]
    assert len(cell.config["layer_types"]) == 48  # copied whole


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's config, unchanged but the three that
    ``reduced`` lists, whose published values stand beside them."""
    with open(os.path.join(REPO, "benchmark/configs/laguna_s_ep32.json")) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 256, "vocab_size": 100352}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 12544)
    published = dict(
        hidden_size=3072, intermediate_size=12288, num_attention_heads=48,
        num_key_value_heads=8, head_dim=128, num_experts_per_tok=10,
        moe_intermediate_size=1024, shared_expert_intermediate_size=1024,
        sliding_window=512, moe_routed_scaling_factor=2.5,
        rms_norm_eps=1e-6, max_position_embeddings=1048576,
        norm_topk_prob=True, tie_word_embeddings=False, gating="per-head")
    for key, value in published.items():
        assert config[key] == value, key
    assert config["rope_parameters"]["full_attention"]["factor"] == 128
    assert config["parameters_held"]["total"] == 811_017_216
    assert config["departs"] == [] and len(config["assumed"]) >= 7
    assert "32 chips" in config["deployment"]
    # The parameter count, from the model's own shapes.
    cell = spec.load_cell(CELL)
    model = family.make_model(cell.config, cell.traffic)
    shapes = jax.eval_shape(
        lambda k: family.init_variables(model, k, cell.config,
                                        cell.traffic)[0],
        jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == \
        811_017_216
