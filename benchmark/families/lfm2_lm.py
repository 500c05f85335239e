"""Family ``lfm2_lm``: ``horovod_tpu.models.Decoder`` as LFM2's sparse
hybrid (gated short convolutions and full attention over grouped heads
with normed queries and keys, two leading dense SwiGLU layers, then
sparse layers of sigmoid-routed experts of which this chip holds a share,
no shared expert, an embedding tied to the head) trained on next-token
cross-entropy over every position of a vocabulary slice.

Layer l (its published index) is ``h = x + Op(RMSNorm(x))``, ``out = h +
FFN(RMSNorm(h))``. Op is, by ``layer_types[l]``: ``conv``, the gated short
convolution ``(C * z) W_out`` of ``[B | C | x] = u W_in``, ``z`` the
causal depthwise convolution of ``B * x`` over ``conv_L_cache`` taps, no
bias; or ``full_attention``, causal attention whose q and k heads are
RMS-normed over their own dims before the rotary positions. FFN is dense
where l < ``num_dense_layers``, else the top-``num_experts_per_tok`` of
``sigmoid(h Wr) + expert_bias`` over all experts, weighted by their
scores over the scores' sum plus ``router_eps``.

An item is a token. The layers held are ``layers_held``, published
indices. The extra state carries the routing counters of the last step,
as ``decoder_lm``'s does: per sparse layer, the assignments each held
expert got (``expert_kept``, int32 (layers, held)) and those routed to
other chips' experts (``expert_elsewhere``, int32 (layers,)). The
functions here run inside the harness's jitted calls: nothing is made on
the host.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.harness import spec

ITEM = "tokens"

_decoder = spec.load_module("families", "decoder_lm")
#: The decoder family's own: token ids uniform over the vocabulary slice
#: held here; (loss, the step's routing counters) of one per-chip batch,
#: next-token cross-entropy over every position.
make_batch = _decoder.make_batch
loss_fn = _decoder.loss_fn


def items_per_sample(config: dict, traffic: dict) -> int:
    return int(traffic["seq_len"])


def _sparse(config: dict, l: int) -> bool:
    return l >= config["num_dense_layers"]


def kinds(config: dict) -> str:
    """The operators of the layers held, a character each: ``C`` gated
    short convolution, ``A`` full attention."""
    return "".join("C" if config["layer_types"][l] == "conv" else "A"
                   for l in config["layers_held"])


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def make_model(config: dict, traffic: dict):
    from horovod_tpu.models import (Decoder, DecoderConfig, LayerSpec,
                                    RopeSpec)

    if traffic["attention"] != "flash":
        raise ValueError(
            f"traffic attention {traffic['attention']!r}: the decoder's "
            "grouped heads exist in the flash kernels only")
    if len(config["layers_held"]) != config["num_hidden_layers"]:
        raise ValueError("layers_held names another number of layers than "
                         "num_hidden_layers")
    if config["conv_bias"] or not config["use_expert_bias"] \
            or not config["norm_topk_prob"] \
            or set(config["layer_types"]) != {"conv", "full_attention"}:
        raise ValueError("want convolutions without bias, an expert bias, "
                         "the chosen weights renormalised and conv and full "
                         "attention layers only")
    d = head_dim(config)
    return Decoder(DecoderConfig(
        vocab_size=config["vocab_size"],
        hidden_dim=config["hidden_size"],
        head_dim=d,
        num_kv_heads=config["num_key_value_heads"],
        layers=tuple(
            LayerSpec("conv", 0, "sparse" if _sparse(config, l) else "dense")
            if kind == "C" else
            LayerSpec("full", config["num_attention_heads"],
                      "sparse" if _sparse(config, l) else "dense")
            for l, kind in zip(config["layers_held"], kinds(config))),
        mlp_dim=config["intermediate_size"],
        window=0,
        rope_full=RopeSpec(theta=float(config["rope_theta"]), rotary_dim=d),
        rope_window=None,
        num_experts=config["published"]["num_experts"],
        experts_held=config["num_experts"],
        first_expert=config["first_expert"],
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        shared_dim=0,
        routed_scaling=float(config["routed_scaling_factor"]),
        rms_eps=config["norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(traffic["remat"]),
        head_gate=False,
        qk_norm=True,
        router="sigmoid",
        router_eps=float(config["router_eps"]),
        tie_head=True,
        layer_ids=tuple(config["layers_held"]),
        conv_kernel=config["conv_L_cache"]))


def _counters(config: dict):
    sparse = sum(_sparse(config, l) for l in config["layers_held"])
    return {"expert_kept": jnp.zeros((sparse, config["num_experts"]),
                                     jnp.int32),
            "expert_elsewhere": jnp.zeros((sparse,), jnp.int32)}


def init_variables(model, key, config: dict, traffic: dict):
    """(params, extra state) from ``key``; the extra state is the routing
    counters, zero until a step has run."""
    tokens = jnp.zeros((1, int(traffic["seq_len"])), jnp.int32)
    return model.init(key, tokens)["params"], _counters(config)


def forward_flops_per_item(config: dict, traffic: dict) -> float:
    """Matmul FLOPs of the forward pass for one token, from shapes: causal
    attention by its s(s+1)/2 visible pairs, the routed experts at the
    share of assignments a uniform router sends to the experts held (top-k
    x held / all). The convolution's taps, the products with B and C,
    lookups, norms and rotary positions multiply no matrices."""
    h, s = config["hidden_size"], int(traffic["seq_len"])
    heads, kv, d = (config["num_attention_heads"],
                    config["num_key_value_heads"], head_dim(config))
    experts = config["published"]["num_experts"]
    routed = config["num_experts_per_tok"] * config["num_experts"] / experts
    total = 2.0 * h * config["vocab_size"]  # the tied head
    for l, kind in zip(config["layers_held"], kinds(config)):
        if kind == "C":
            total += 2 * h * 3 * h + 2 * h * h  # in_proj, out_proj
        else:
            total += (2 * h * heads * d * 2     # query and out projections
                      + 2 * h * kv * d * 2      # key and value
                      # q.k^T and p.v, 2.d each, over the visible pairs
                      + 4 * d * heads * (s + 1) / 2)
        if _sparse(config, l):
            total += (2 * h * experts           # the router
                      + routed * 3 * 2 * h * config["moe_intermediate_size"])
        else:
            total += 3 * 2 * h * config["intermediate_size"]
    return float(total)


def model_flops_per_item(config: dict, traffic: dict) -> float:
    """FLOPs the forward and backward passes need for one token: no
    optimizer, no recompute. Backward is twice forward."""
    return 3.0 * forward_flops_per_item(config, traffic)
