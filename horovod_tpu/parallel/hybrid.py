"""A full hybrid-parallel (dp × pp × tp × sp) transformer training step.

Composes every strategy in this package into one compiled SPMD program:

- **dp**: batch sharded; gradients pmean'd (the horovod verb).
- **pp**: encoder layers split into GPipe stages (:mod:`.pipeline`).
- **tp**: attention projections and MLP are Megatron-sharded
  (:mod:`.tensor_parallel`); one forward psum per block half.
- **sp**: sequence sharded; attention is exact ring attention
  (:mod:`.ring_attention`) — K/V blocks rotate over ICI neighbours.

Parameter placement: stage params live on their pp rank, tp-sharded leaves
are per-chip shards, everything is replicated across dp and sp. Gradient
reduction is therefore pmean over (dp, sp) for stage params and the head,
plus a psum over pp for the embeddings (they contribute only on stage 0).

This powers ``__graft_entry__.dryrun_multichip`` and serves as the
reference recipe for users composing their own hybrid steps.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.parallel.mesh import hybrid_mesh
from horovod_tpu.parallel.moe import moe_layer
from horovod_tpu.parallel.pipeline import pipeline_apply
from horovod_tpu.parallel.ring_attention import ring_attention
from horovod_tpu.parallel.tensor_parallel import (
    ColumnParallelDense,
    ParallelMLP,
    RowParallelDense,
)


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 64
    hidden_dim: int = 32
    mlp_dim: int = 64
    num_heads: int = 4
    layers_per_stage: int = 1
    seq_len: int = 16          # global sequence length
    microbatches: int = 2
    lr: float = 0.1
    dtype: object = jnp.float32
    # Expert-parallel MoE block per layer (experts sharded over 'ep').
    use_moe: bool = True
    experts_per_chip: int = 2
    moe_capacity_factor: float = 2.0


def partition_axes(n: int) -> dict:
    """Factor ``n`` devices into (dp, pp, tp, sp, ep): powers of two feed
    the model axes first (pp, tp, sp, then ep), any remainder rides dp.
    Axes the budget can't fill stay at size 1 — their collectives become
    no-ops but the sharding structure is identical."""
    sizes = {"dp": 1, "pp": 1, "tp": 1, "sp": 1, "ep": 1}
    rem = n
    for ax in ("pp", "tp", "sp", "ep"):
        if rem % 2 == 0 and rem > 1:
            sizes[ax] = 2
            rem //= 2
    sizes["dp"] = rem
    return sizes


class HybridStage(nn.Module):
    """One pipeline stage: ``layers_per_stage`` pre-norm transformer layers
    with tp-sharded projections and ring attention over sp."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        tp = lax.psum(1, "tp")
        heads_local = cfg.num_heads // tp
        head_dim = cfg.hidden_dim // cfg.num_heads
        for i in range(cfg.layers_per_stage):
            h = nn.LayerNorm(dtype=cfg.dtype, name=f"ln_attn_{i}")(x)
            qkv = [
                ColumnParallelDense(
                    cfg.num_heads * head_dim, "tp", dtype=cfg.dtype,
                    name=f"{nm}_{i}")(h)
                for nm in ("q", "k", "v")
            ]
            q, k, v = (
                t.reshape(t.shape[:-1] + (heads_local, head_dim))
                for t in qkv
            )
            a = ring_attention(q, k, v, "sp", causal=True)
            a = a.reshape(a.shape[:-2] + (heads_local * head_dim,))
            a = RowParallelDense(cfg.hidden_dim, "tp", dtype=cfg.dtype,
                                 name=f"attn_out_{i}")(a)
            x = x + a
            h = nn.LayerNorm(dtype=cfg.dtype, name=f"ln_mlp_{i}")(x)
            x = x + ParallelMLP(cfg.hidden_dim, cfg.mlp_dim, "tp",
                                dtype=cfg.dtype, name=f"mlp_{i}")(h)
            if cfg.use_moe:
                # Expert-parallel MoE block: experts sharded over 'ep'.
                # Tokens are replicated across ep in this recipe (they are
                # sharded over dp and sp only), so each ep chip routes the
                # same tokens and expert compute is duplicated ep-fold —
                # correct but redundant. Production deployments map the ep
                # groups onto dp groups so tokens arrive pre-sharded; kept
                # simple here because it leaves gradient reduction uniform
                # (see reduce_grads). The load-balance aux loss is dropped
                # (pipeline activations must be shape-invariant).
                ep = lax.psum(1, "ep")
                ep_idx = lax.axis_index("ep")
                e_local = cfg.experts_per_chip

                def _expert_init(key, shape, dtype):
                    # Experts are *sharded* over ep: distinct weights per
                    # ep chip. Everything else in the stage (router,
                    # attention, MLP, norms) must stay REPLICATED across
                    # ep — the module init key is identical across ep, and
                    # only expert leaves fold the ep index in.
                    return nn.initializers.lecun_normal()(
                        jax.random.fold_in(key, ep_idx), shape, dtype)

                h = nn.LayerNorm(dtype=cfg.dtype, name=f"ln_moe_{i}")(x)
                router = self.param(
                    f"moe_router_{i}", nn.initializers.lecun_normal(),
                    (cfg.hidden_dim, e_local * ep), jnp.float32)
                wi = self.param(
                    f"moe_wi_{i}", _expert_init,
                    (e_local, cfg.hidden_dim, cfg.mlp_dim), jnp.float32)
                wo = self.param(
                    f"moe_wo_{i}", _expert_init,
                    (e_local, cfg.mlp_dim, cfg.hidden_dim), jnp.float32)
                b, s, hid = h.shape
                y, _aux = moe_layer(
                    h.reshape(b * s, hid), router, wi, wo, "ep",
                    capacity_factor=cfg.moe_capacity_factor)
                x = x + y.reshape(b, s, hid).astype(x.dtype)
        return x


def build_train_step(mesh: Mesh, cfg: HybridConfig):
    """Return ``(step, token_spec)`` where ``step(tokens, key) ->
    (loss_before, loss_after)`` initializes hybrid-sharded parameters,
    takes one full SGD step, and re-evaluates — all inside a single
    compiled SPMD program over ``mesh``. Required axes: dp/pp/tp/sp, plus
    ``ep`` when ``cfg.use_moe`` (the only place the ep axis is touched)."""
    cfg_stage = HybridStage(cfg)

    def spmd(tokens, key):
        dp = lax.psum(1, "dp")
        pp = lax.psum(1, "pp")
        sp = lax.psum(1, "sp")
        pp_idx = lax.axis_index("pp")
        sp_idx = lax.axis_index("sp")
        tp_idx = lax.axis_index("tp")
        b_local, s_local = tokens.shape
        m = cfg.microbatches
        bm = b_local // m

        # Distinct init per (pp stage, tp shard); identical across
        # dp/sp/ep — expert weights alone diverge per ep chip, via their
        # own initializer (see HybridStage._expert_init). Folding ep here
        # would make the router/attention/MLP weights diverge across ep,
        # silently desynchronizing the replicas.
        stage_key = jax.random.fold_in(
            jax.random.fold_in(key, pp_idx), tp_idx)
        dummy = jnp.zeros((bm, s_local, cfg.hidden_dim), cfg.dtype)
        stage_params = cfg_stage.init(stage_key, dummy)["params"]
        ek = jax.random.split(key, 3)
        embed = jax.random.normal(
            ek[0], (cfg.vocab_size, cfg.hidden_dim), cfg.dtype) * 0.02
        pos = jax.random.normal(
            ek[1], (cfg.seq_len, cfg.hidden_dim), cfg.dtype) * 0.02
        head = jax.random.normal(
            ek[2], (cfg.hidden_dim, cfg.vocab_size), cfg.dtype) * 0.02
        params = {"embed": embed, "pos": pos, "head": head,
                  "stage": stage_params}

        def loss_fn(params):
            x = params["embed"][tokens]
            pos_slice = lax.dynamic_slice_in_dim(
                params["pos"], sp_idx * s_local, s_local, axis=0)
            x = x + pos_slice[None]
            micro = x.reshape((m, bm, s_local, cfg.hidden_dim))
            out = pipeline_apply(
                lambda p, a: cfg_stage.apply({"params": p}, a),
                params["stage"], micro, "pp")
            out = out.reshape((b_local, s_local, cfg.hidden_dim))
            logits = (out @ params["head"]).astype(jnp.float32)
            # Next-token prediction. The target for the last position of
            # each sp shard is the NEXT shard's first token, fetched over
            # ICI via ppermute (shard j sends its first column to shard
            # j-1); the global last position has no next token and is
            # masked out of the loss.
            nxt_first = lax.ppermute(
                tokens[:, :1], "sp",
                [(j, (j - 1) % sp) for j in range(sp)])
            tgt = jnp.concatenate([tokens[:, 1:], nxt_first], axis=1)
            ll = jax.nn.log_softmax(logits)
            tok_loss = -jnp.take_along_axis(ll, tgt[..., None], axis=-1)[..., 0]
            pos_ids = sp_idx * s_local + jnp.arange(s_local)
            mask = (pos_ids < cfg.seq_len - 1).astype(tok_loss.dtype)
            num = lax.psum((tok_loss * mask[None, :]).sum(), ("dp", "sp"))
            den = lax.psum(jnp.float32(b_local) * mask.sum(), ("dp", "sp"))
            return num / den

        def reduce_grads(g):
            # Stage/head: replicated over dp+sp -> pmean. Embeddings feed
            # only stage-0 activations -> also psum over pp.
            g = jax.tree.map(lambda t: lax.pmean(t, ("dp", "sp")), g)
            g["embed"] = lax.psum(g["embed"], "pp")
            g["pos"] = lax.psum(g["pos"], "pp")
            return g

        loss0, grads = jax.value_and_grad(loss_fn)(params)
        grads = reduce_grads(grads)
        params = jax.tree.map(lambda p, g: p - cfg.lr * g, params, grads)
        loss1 = loss_fn(params)
        # pmean over the remaining axes so every chip returns the same
        # replicated scalar.
        return (lax.pmean(loss0, ("pp", "tp")),
                lax.pmean(loss1, ("pp", "tp")))

    token_spec = P(("dp",), ("sp",))
    step = jax.jit(_shard_map(
        spmd, mesh=mesh, in_specs=(token_spec, P()),
        out_specs=(P(), P()), check_vma=False))
    return step, token_spec


def dryrun(n_devices: int, devices=None,
           cfg: HybridConfig = HybridConfig()) -> Tuple[float, float]:
    """Build the mesh, run one hybrid step, return (loss_before,
    loss_after)."""
    import numpy as np

    if devices is None:
        devices = jax.devices()
    sizes = partition_axes(n_devices)
    mesh = hybrid_mesh(sizes, devices[:n_devices])
    dp, sp = sizes["dp"], sizes["sp"]
    batch = 2 * cfg.microbatches * dp
    if cfg.seq_len % sp:
        raise ValueError("seq_len must divide by sp")
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(batch, cfg.seq_len)).astype(np.int32)
    step, _ = build_train_step(mesh, cfg)
    l0, l1 = step(tokens, jax.random.PRNGKey(0))
    return float(l0), float(l1)
