"""Family ``transformer_lm``: ``horovod_tpu.models.TransformerLM`` trained
on next-token cross-entropy over every position, as
``chip_smoke.bert_phase`` and ``examples/bert_pretraining_benchmark.py``
build it.

An item is a token. The functions here run inside the harness's jitted
calls: nothing is made on the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

ITEM = "tokens"


def _attention_fn(name: str):
    if name == "stock":
        return None  # the model's default: XLA softmax attention
    if name == "flash":
        from horovod_tpu.ops.flash_attention import flash_attention

        return flash_attention
    raise ValueError(f"traffic attention {name!r}: want 'stock' or 'flash'")


def items_per_sample(config: dict, traffic: dict) -> int:
    return int(traffic["seq_len"])


def make_model(config: dict, traffic: dict):
    from horovod_tpu.models import TransformerConfig, TransformerLM

    seq = int(traffic["seq_len"])
    return TransformerLM(TransformerConfig(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        hidden_dim=config["hidden_size"],
        mlp_dim=config["intermediate_size"],
        # The position table is widened to the cell's sequence length
        # where that exceeds the published one (``assumed`` in the file).
        max_len=max(seq, config["max_position_embeddings"]),
        dropout_rate=0.0,
        dtype=jnp.dtype(config["compute_dtype"]),
        attention_fn=_attention_fn(traffic["attention"]),
        remat=bool(traffic["remat"])))


def init_variables(model, key, config: dict, traffic: dict):
    """(params, extra state) from ``key``; the family keeps no state
    beside its parameters."""
    tokens = jnp.zeros((1, int(traffic["seq_len"])), jnp.int32)
    return model.init(key, tokens)["params"], {}


def make_batch(key, n_samples: int, config: dict, traffic: dict):
    return (jax.random.randint(
        key, (n_samples, int(traffic["seq_len"])), 0, config["vocab_size"],
        jnp.int32),)


def loss_fn(model, params, extra, batch):
    """(loss, new extra state) of one per-chip batch."""
    (tokens,) = batch
    logits = model.apply({"params": params}, tokens)
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.roll(tokens, -1, axis=1)).mean()
    return loss, extra


def forward_flops_per_item(config: dict, traffic: dict) -> float:
    """Matmul FLOPs of the forward pass for one token, from shapes, with
    the full s x s attention (the encoder is not causal). The embedding
    lookups multiply nothing."""
    h = config["hidden_size"]
    m = config["intermediate_size"]
    s = int(traffic["seq_len"])
    per_layer = (
        8 * h * h      # query, key, value, out projections
        + 4 * h * m    # the two MLP matmuls
        + 4 * s * h)   # q.k^T and p.v over s keys, all heads
    return float(config["num_hidden_layers"] * per_layer
                 + 2 * h * config["vocab_size"])  # LM head


def model_flops_per_item(config: dict, traffic: dict) -> float:
    """FLOPs the forward and backward passes need for one token: no
    optimizer, no recompute. Backward is twice forward."""
    return 3.0 * forward_flops_per_item(config, traffic)
