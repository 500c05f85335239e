"""Family ``hybrid_lm``: ``horovod_tpu.models.HybridLM`` (blocks of one
mixer each by the published pattern: ``M`` state-space mixers with a
chunked scan, ``*`` causal attention over grouped key-value heads, ``E``
latent sparse-expert blocks of which this chip holds a share) trained on
next-token cross-entropy over every position of a vocabulary slice.

An item is a token. The head counts of the configuration are this chip's
share (``reduced``; ``published`` has the model's). The extra state
carries the routing counters of the last step: per ``E`` block, the
assignments each held expert got (``expert_kept``, int32 (blocks, held))
and those routed to other chips' experts (``expert_elsewhere``, int32
(blocks,)). The functions here run inside the harness's jitted calls:
nothing is made on the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

ITEM = "tokens"


def items_per_sample(config: dict, traffic: dict) -> int:
    return int(traffic["seq_len"])


def pattern(config: dict) -> str:
    """The mixers of the blocks held: the first ``num_hidden_layers``
    characters of the published pattern."""
    return config["hybrid_override_pattern"][:config["num_hidden_layers"]]


def make_model(config: dict, traffic: dict):
    from horovod_tpu.models import HybridConfig, HybridLM

    if traffic["attention"] != "flash":
        raise ValueError(
            f"traffic attention {traffic['attention']!r}: the hybrid's "
            "grouped heads exist in the flash kernels only")
    if (config["n_group"], config["topk_group"]) != (1, 1):
        raise ValueError("routing by groups of experts is not written: the "
                         "configuration has one group")
    if config["mlp_hidden_act"] != "relu2" or not config["norm_topk_prob"]:
        raise ValueError("want relu2 experts and top-k weights renormalised")
    return HybridLM(HybridConfig(
        vocab_size=config["vocab_size"],
        hidden_dim=config["hidden_size"],
        pattern=pattern(config),
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssm_groups=config["n_groups"],
        ssm_state=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"],
        chunk_size=config["chunk_size"],
        dt_min=config["time_step_min"], dt_max=config["time_step_max"],
        dt_floor=config["time_step_floor"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        num_experts=config["published"]["n_routed_experts"],
        experts_held=config["n_routed_experts"],
        first_expert=config["first_expert"],
        top_k=config["num_experts_per_tok"],
        latent_dim=config["moe_latent_size"],
        expert_dim=config["moe_intermediate_size"],
        shared_dim=config["moe_shared_expert_intermediate_size"],
        routed_scaling=float(config["routed_scaling_factor"]),
        rms_eps=config["layer_norm_epsilon"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(traffic["remat"])))


def _counters(config: dict):
    sparse = pattern(config).count("E")
    return {"expert_kept": jnp.zeros((sparse, config["n_routed_experts"]),
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


def scan_flops_per_item(config: dict) -> float:
    """Products of one ``M`` block's scan for one token in its chunked
    form at the published chunk Q: the scores C.B^T of a group (2 Q N),
    scores against x a head (2 Q P), the chunk's end state and the
    entering state's part of the output (2 N P each a head)."""
    q, n = config["chunk_size"], config["ssm_state_size"]
    p, heads = config["mamba_head_dim"], config["mamba_num_heads"]
    return float(2 * q * n * config["n_groups"] + 2 * q * p * heads
                 + 4 * n * p * heads)


def forward_flops_per_item(config: dict, traffic: dict) -> float:
    """Matmul FLOPs of the forward pass for one token, from shapes: the
    scan in its chunked form, causal attention by its visible pairs, the
    routed experts at the share of assignments a uniform router sends to
    the experts held (top-k x held / all). Lookups, norms, the depthwise
    convolution and the activations multiply no matrices."""
    h, s = config["hidden_size"], int(traffic["seq_len"])
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    bc = config["n_groups"] * config["ssm_state_size"]
    q_heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, latent = config["head_dim"], config["moe_latent_size"]
    routed = (config["num_experts_per_tok"] * config["n_routed_experts"]
              / config["published"]["n_routed_experts"])
    per_block = {
        "M": (2 * h * (2 * heads * p + 2 * bc + heads)  # in_proj
              + 2 * heads * p * h                       # out_proj
              + scan_flops_per_item(config)),
        "*": (2 * h * q_heads * d * 2                   # query and out
              + 2 * h * kv * d * 2                      # key and value
              # q.k^T and p.v, 2.d each, over the s(s+1)/2 visible pairs
              + 4 * d * q_heads * (s + 1) / 2),
        "E": (2 * h * config["published"]["n_routed_experts"]  # router
              + 2 * 2 * h * latent                      # into and out of it
              + 2 * 2 * h * config["moe_shared_expert_intermediate_size"]
              + routed * 2 * 2 * latent * config["moe_intermediate_size"]),
    }
    return float(2.0 * h * config["vocab_size"]         # LM head
                 + sum(per_block[kind] for kind in pattern(config)))


def model_flops_per_item(config: dict, traffic: dict) -> float:
    """FLOPs the forward and backward passes need for one token: no
    optimizer, no recompute. Backward is twice forward."""
    return 3.0 * forward_flops_per_item(config, traffic)
