"""Family ``sambay_lm``: ``horovod_tpu.models.SambaYLM`` (a self-decoder
of selective-scan and window differential-attention layers, one full
differential-attention layer, and a cross-decoder of gated memory units
and cross differential attention that read what layers n / 2 and n / 2 +
1 made; LayerNorm, SwiGLU, an embedding tied to the head) trained on
next-token cross-entropy over every position of a vocabulary slice.

An item is a token. The layers held are ``layers_held``, published
indices; the kind of each follows from its index by the published rule
(``horovod_tpu.models.sambay.layer_kind``). The model routes nothing, so
the extra state is empty. The functions here run inside the harness's
jitted calls: nothing is made on the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

ITEM = "tokens"


def items_per_sample(config: dict, traffic: dict) -> int:
    return int(traffic["seq_len"])


def kinds(config: dict) -> str:
    """The mixers of the layers held, a character each: ``M`` selective
    scan, ``S`` window and ``F`` full differential attention, ``G`` gated
    memory unit, ``X`` cross differential attention."""
    from horovod_tpu.models.sambay import layer_kind

    return "".join(
        layer_kind(l, config["published"]["num_hidden_layers"],
                   config["mb_per_layer"]) for l in config["layers_held"])


def make_model(config: dict, traffic: dict):
    from horovod_tpu.models import SambaYConfig, SambaYLM

    if traffic["attention"] != "flash":
        raise ValueError(
            f"traffic attention {traffic['attention']!r}: differential "
            "attention's window and grouped heads exist in the flash "
            "kernels only")
    if len(config["layers_held"]) != config["num_hidden_layers"]:
        raise ValueError("layers_held names another number of layers than "
                         "num_hidden_layers")
    if not config["tie_word_embeddings"] or config["mlp_bias"] \
            or config["lm_head_bias"] or config["hidden_act"] != "silu" \
            or config["embd_pdrop"] or config["resid_pdrop"]:
        raise ValueError("want a tied head, a SiLU-gated MLP and head "
                         "without bias and no dropout")
    return SambaYLM(SambaYConfig(
        vocab_size=config["vocab_size"],
        hidden_dim=config["hidden_size"],
        num_layers=config["published"]["num_hidden_layers"],
        layers=tuple(config["layers_held"]),
        mlp_dim=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        window=config["sliding_window"],
        mb_per_layer=config["mb_per_layer"],
        ssm_state=config["mamba_d_state"],
        ssm_expand=config["mamba_expand"],
        dt_rank=config["mamba_dt_rank"],
        conv_kernel=config["mamba_d_conv"],
        chunk_size=config["scan_chunk"],
        dt_min=config["time_step_min"], dt_max=config["time_step_max"],
        dt_floor=config["time_step_floor"],
        ln_eps=config["layer_norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(traffic["remat"])))


def init_variables(model, key, config: dict, traffic: dict):
    """(params, extra state) from ``key``; the family keeps no state
    beside its parameters."""
    tokens = jnp.zeros((1, int(traffic["seq_len"])), jnp.int32)
    return model.init(key, tokens)["params"], {}


def make_batch(key, n_samples: int, config: dict, traffic: dict):
    """Token ids uniform over the vocabulary slice held here."""
    return (jax.random.randint(
        key, (n_samples, int(traffic["seq_len"])), 0, config["vocab_size"],
        jnp.int32),)


def loss_fn(model, params, extra, batch):
    """(loss, the unchanged extra state) of one per-chip batch."""
    (tokens,) = batch
    logits = model.apply({"params": params}, tokens)
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.roll(tokens, -1, axis=1)).mean()
    return loss, extra


def visible_pairs(s: int, window=None) -> int:
    """(query, key) pairs a causal layer attends over ``s`` positions:
    s(s+1)/2, or sum_i min(i+1, window) with a window."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def attention_flops_per_pair(config: dict) -> float:
    """Products of differential attention's mathematics for one visible
    (query, key) pair of one layer, forward: for each pair of query
    heads, two score maps (2 d each) and each map against a value 2 d
    wide (4 d each). The four kernel calls that compute it today make
    each score map twice; the mathematics does not."""
    heads = config["num_attention_heads"]
    d = config["hidden_size"] // heads
    return float(heads // 2 * (2 * 2 * d + 2 * 2 * 2 * d))


def forward_flops_per_item(config: dict, traffic: dict) -> float:
    """Matmul FLOPs of the forward pass for one token, from shapes:
    differential attention by its visible pairs and its mathematics
    (:func:`attention_flops_per_pair`). Lookups, norms, the depthwise
    convolution, the selective scan (``T C N`` state updates on the
    vector units) and the gates multiply no matrices."""
    h, s = config["hidden_size"], int(traffic["seq_len"])
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = h // heads
    inner, n = config["mamba_expand"] * h, config["mamba_d_state"]
    rank = config["mamba_dt_rank"]
    per_pair = attention_flops_per_pair(config)
    full = per_pair * visible_pairs(s) / s
    per_mixer = {
        "M": (2 * h * 2 * inner + 2 * inner * (rank + 2 * n)
              + 2 * rank * inner + 2 * inner * h),
        "S": (2 * h * (heads + 2 * kv) * d + 2 * h * h
              + per_pair * visible_pairs(s, config["sliding_window"]) / s),
        "F": 2 * h * (heads + 2 * kv) * d + 2 * h * h + full,
        "G": 2 * 2 * h * inner,
        "X": 2 * 2 * h * h + full,
    }
    mlp = 3 * 2 * h * config["intermediate_size"]
    return float(2.0 * h * config["vocab_size"]          # the tied head
                 + sum(per_mixer[kind] + mlp for kind in kinds(config)))


def model_flops_per_item(config: dict, traffic: dict) -> float:
    """FLOPs the forward and backward passes need for one token: no
    optimizer, no recompute. Backward is twice forward."""
    return 3.0 * forward_flops_per_item(config, traffic)
