"""Family ``decoder_lm``: ``horovod_tpu.models.Decoder`` (layers that
differ: full and window causal attention over grouped key-value heads,
head counts by layer, a dense SwiGLU layer and sparse-expert layers of
which this chip holds a share) trained on next-token cross-entropy over
every position of a vocabulary slice.

An item is a token. The extra state carries the routing counters of the
last step: per sparse layer, the assignments each held expert got
(``expert_kept``, int32 (layers, held)) and those routed to other chips'
experts (``expert_elsewhere``, int32 (layers,)). The functions here run
inside the harness's jitted calls: nothing is made on the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

ITEM = "tokens"

KINDS = {"full_attention": "full", "sliding_attention": "window"}


def items_per_sample(config: dict, traffic: dict) -> int:
    return int(traffic["seq_len"])


def layer_kinds(config: dict):
    """(attention kind, query heads, mlp kind) of the layers held: the
    first ``num_hidden_layers`` entries of the published lists."""
    n = config["num_hidden_layers"]
    return list(zip(
        (KINDS[k] for k in config["layer_types"][:n]),
        config["num_attention_heads_per_layer"][:n],
        config["mlp_layer_types"][:n]))


def _rope(rope: dict, head_dim: int):
    from horovod_tpu.models import RopeSpec

    rotary = int(head_dim * rope["partial_rotary_factor"])
    if rope["rope_type"] == "default":
        return RopeSpec(theta=float(rope["rope_theta"]), rotary_dim=rotary)
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}: want 'default' "
                         "or 'yarn'")
    return RopeSpec(
        theta=float(rope["rope_theta"]), rotary_dim=rotary,
        yarn_factor=float(rope["factor"]),
        original_max_len=int(rope["original_max_position_embeddings"]),
        beta_fast=float(rope["beta_fast"]),
        beta_slow=float(rope["beta_slow"]),
        attention_factor=float(rope["attention_factor"]))


def make_model(config: dict, traffic: dict):
    from horovod_tpu.models import Decoder, DecoderConfig, LayerSpec

    if traffic["attention"] != "flash":
        raise ValueError(
            f"traffic attention {traffic['attention']!r}: the decoder's "
            "window and grouped heads exist in the flash kernels only")
    if config.get("moe_router_logit_softcapping") or config.get(
            "moe_apply_router_weight_on_input"):
        raise ValueError("router softcapping and weights on the input are "
                         "not written: the configuration has neither")
    rope = config["rope_parameters"]
    return Decoder(DecoderConfig(
        vocab_size=config["vocab_size"],
        hidden_dim=config["hidden_size"],
        head_dim=config["head_dim"],
        num_kv_heads=config["num_key_value_heads"],
        layers=tuple(LayerSpec(*kind) for kind in layer_kinds(config)),
        mlp_dim=config["intermediate_size"],
        window=config["sliding_window"],
        rope_full=_rope(rope["full_attention"], config["head_dim"]),
        rope_window=_rope(rope["sliding_attention"], config["head_dim"]),
        num_experts=config["published"]["num_experts"],
        experts_held=config["num_experts"],
        first_expert=config["first_expert"],
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        shared_dim=config["shared_expert_intermediate_size"],
        routed_scaling=float(config["moe_routed_scaling_factor"]),
        rms_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(traffic["remat"])))


def _counters(config: dict):
    sparse = sum(kind[2] == "sparse" for kind in layer_kinds(config))
    return {"expert_kept": jnp.zeros((sparse, config["num_experts"]),
                                     jnp.int32),
            "expert_elsewhere": jnp.zeros((sparse,), jnp.int32)}


def init_variables(model, key, config: dict, traffic: dict):
    """(params, extra state) from ``key``; the extra state is the routing
    counters, zero until a step has run."""
    tokens = jnp.zeros((1, int(traffic["seq_len"])), jnp.int32)
    return model.init(key, tokens)["params"], _counters(config)


def make_batch(key, n_samples: int, config: dict, traffic: dict):
    """Token ids uniform over the vocabulary slice held here."""
    return (jax.random.randint(
        key, (n_samples, int(traffic["seq_len"])), 0, config["vocab_size"],
        jnp.int32),)


def loss_fn(model, params, extra, batch):
    """(loss, the step's routing counters) of one per-chip batch."""
    (tokens,) = batch
    logits, counters = model.apply({"params": params}, tokens,
                                   return_counters=True)
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.roll(tokens, -1, axis=1)).mean()
    return loss, counters


def visible_pairs(s: int, window=None) -> int:
    """(query, key) pairs a causal layer attends over ``s`` positions:
    s(s+1)/2, or sum_i min(i+1, window) with a window."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def forward_flops_per_item(config: dict, traffic: dict) -> float:
    """Matmul FLOPs of the forward pass for one token, from shapes:
    causal and banded attention by their visible pairs, the routed experts
    at the share of assignments a uniform router sends to the experts
    held (top-k x held / all). Lookups, rotary positions, norms and gates'
    sigmoids multiply nothing."""
    h, d = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    s = int(traffic["seq_len"])
    expert = 3 * 2 * h * config["moe_intermediate_size"]
    routed = (config["num_experts_per_tok"] * config["num_experts"]
              / config["published"]["num_experts"])
    total = 2.0 * h * config["vocab_size"]  # LM head
    for attention, heads, mlp in layer_kinds(config):
        window = config["sliding_window"] if attention == "window" else None
        total += (2 * h * heads * d * 2        # query and out projections
                  + 2 * h * kv * d * 2         # key and value
                  + 2 * h * heads              # the heads' gate
                  # q.k^T and p.v, 2.d each, over the visible pairs
                  + 4 * d * heads * visible_pairs(s, window) / s)
        if mlp == "dense":
            total += 3 * 2 * h * config["intermediate_size"]
        else:
            total += (2 * h * config["published"]["num_experts"]  # router
                      + 3 * 2 * h * config["shared_expert_intermediate_size"]
                      + routed * expert)
    return float(total)


def model_flops_per_item(config: dict, traffic: dict) -> float:
    """FLOPs the forward and backward passes need for one token: no
    optimizer, no recompute. Backward is twice forward."""
    return 3.0 * forward_flops_per_item(config, traffic)
