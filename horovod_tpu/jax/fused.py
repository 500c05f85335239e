"""Trace-time fusion of the optimizer update — tensor fusion (reference:
horovod/common/fusion_buffer_manager.cc, operations.cc:2035-2074) applied
to the *parameter update* instead of the wire.

Why this exists: a ResNet-50 step updates ~160 parameter tensors, ~110 of
them tiny (BN scales/biases are 64-2048 floats). XLA lowers one fusion per
tensor, and on TPU each carries a fixed dispatch + HBM round-trip cost.
Concatenating the small ones of each dtype into a single flat vector turns
~110 launches into a couple of big bandwidth-bound fusions — the
economics of the reference's 64 MB fusion buffer, resolved at compile
time.

Why only the SMALL ones: large tensors gain nothing from packing (they
are already bandwidth-bound) and lose a lot — XLA fuses a weight-grad
convolution directly into its momentum/param update when the update
consumes the conv's output per-tensor; routing it through a concatenated
buffer severs that producer-consumer fusion and adds a full extra
HBM round-trip per step (measured: whole-tree packing REGRESSED ResNet-50
bs32 from 12.1 to 13.5 ms/step; small-only packing is the win). The
``threshold_elems`` knob is the compile-time analogue of the reference's
runtime fusion-threshold byte knob. Its default,
``ops.collectives.FUSION_THRESHOLD_ELEMS``, has a second user: the
gradient exchange inside a traced step
(``ops.collectives.grouped_allreduce``) packs the same leaves and
reduces every larger one as itself, for the same reason on the other
side of the wire.

Correctness domain: any *elementwise* gradient transformation — one where
the update for element ``i`` depends only on gradient/state element ``i``
(sgd, momentum, adam(w), rmsprop, lion, ...). Global-norm clipping also
composes (the norm is global either way). Transforms that inspect
per-parameter *shapes* (adafactor's factored second moments, layerwise
LARS/LAMB trust ratios) must keep the unfused path.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.common import phases as _phases
from horovod_tpu.ops.collectives import (
    FUSION_THRESHOLD_ELEMS as DEFAULT_THRESHOLD_ELEMS,
)

# Spellings accepted for the state_dtype policy knob. None / f32 mean
# "off" (full-width f32 state, the pre-r7 behavior).
_STATE_DTYPE_OFF = (None, "f32", "float32")
_STATE_DTYPE_NAMES = {"bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
                      "f16": jnp.float16, "float16": jnp.float16}


def canonical_state_dtype(state_dtype):
    """Normalize a ``state_dtype`` policy spelling to a jnp dtype, or
    None when the policy is off. Accepts ``None``/``'f32'`` (off),
    ``'bf16'``/``'bfloat16'`` (the TPU-native reduced-precision layout,
    arxiv 1909.09756), ``'f16'``, or a floating jnp/numpy dtype."""
    if state_dtype in _STATE_DTYPE_OFF:
        return None
    if isinstance(state_dtype, str):
        try:
            return _STATE_DTYPE_NAMES[state_dtype]
        except KeyError:
            raise ValueError(
                f"state_dtype={state_dtype!r}: expected one of "
                f"{sorted(_STATE_DTYPE_NAMES)} or 'f32'/None") from None
    dt = jnp.dtype(state_dtype)
    if dt == jnp.dtype(jnp.float32):
        # jnp.float32/np.float32 mean "off", symmetric with the 'f32'
        # string spelling above.
        return None
    if not jnp.issubdtype(dt, jnp.floating) or dt.itemsize >= 4:
        raise ValueError(f"state_dtype={state_dtype!r} is not a "
                         "reduced-precision float dtype")
    return dt


def cast_resident_params(params, state_dtype):
    """Cast a parameter tree's float leaves to the resident ``state_dtype``
    policy width (non-float leaves untouched; identity when the policy is
    off). Call BEFORE ``optimizer.init`` — the f32 master shards (with
    ``sharded_update``) derive from the residents at init. The Trainer and
    bench wiring route through here; exported so third-party training
    loops apply the same rule. NOTE: batch-norm statistics live outside
    the param tree (keep them f32 — running moments accumulate badly in
    bf16)."""
    dtype = canonical_state_dtype(state_dtype)
    if dtype is None:
        return params
    return jax.tree_util.tree_map(
        lambda l: (l.astype(dtype)
                   if jnp.issubdtype(jnp.result_type(l), jnp.floating)
                   else l),
        params)


def _is_stored_leaf(leaf) -> bool:
    """True for the state leaves the storage policy applies to: non-scalar
    float buffers (m/v/trace and the packed param-shaped buffers). Scalar
    bookkeeping (adam's count, schedule steps) stays exact."""
    return (hasattr(leaf, "dtype") and jnp.ndim(leaf) >= 1
            and jnp.issubdtype(jnp.result_type(leaf), jnp.floating))


def store_state(state, dtype):
    """Downcast every non-scalar f32 state leaf to the storage ``dtype``
    — what lives in HBM between steps."""
    if dtype is None:
        return state
    return jax.tree_util.tree_map(
        lambda l: (l.astype(dtype)
                   if _is_stored_leaf(l) and l.dtype == jnp.float32 else l),
        state)


def load_state(state, dtype):
    """Upcast the storage-``dtype`` leaves back to f32 for the update
    math (the converts fuse into the consuming op — no extra HBM pass)."""
    if dtype is None:
        return state
    return jax.tree_util.tree_map(
        lambda l: (l.astype(jnp.float32)
                   if _is_stored_leaf(l) and l.dtype == dtype else l),
        state)


def state_storage(optimizer: optax.GradientTransformation,
                  state_dtype) -> optax.GradientTransformationExtraArgs:
    """Wrap an elementwise optax transform so its state *storage* is
    ``state_dtype`` while its update *math* stays f32: every non-scalar
    float state buffer (momentum, Adam m/v) is downcast after init/update
    and upcast before the inner update runs. The MLPerf TPU recipes'
    bf16-resident layout (arxiv 1909.09756) applied to optimizer state —
    HBM read+write of the state halves, the arithmetic does not change
    dtype. Identity when ``state_dtype`` is None/'f32'.

    NOTE: without a master copy the *parameter* apply still rounds to the
    param dtype — pair with :func:`horovod_tpu.jax.shard_update`'s
    ``state_dtype`` for f32 master shards (docs/troubleshooting.md
    "bf16-state convergence drift"). The numerics observatory watches
    this masterless regime live: under ``HVD_NUMERICS`` the keras Trainer
    feeds the ``numerics.update_ratio`` gauge (||update||/||params||) —
    a sustained ratio below ~1 resident ulp means updates are being
    rounded away (core/numerics.py, docs/observability.md "Numerics")."""
    dtype = canonical_state_dtype(state_dtype)
    if dtype is None:
        return optax.with_extra_args_support(optimizer)
    optimizer = optax.with_extra_args_support(optimizer)

    def init(params):
        return store_state(optimizer.init(params), dtype)

    def update(grads, state, params=None, **extra_args):
        upd, new_state = optimizer.update(grads, load_state(state, dtype),
                                          params, **extra_args)
        # The f32 math would otherwise hand back a full-width f32 update
        # tree; emit updates at the param width (what optax.apply_updates
        # rounds to anyway) — or at the GRAD width when params are
        # omitted (standard optax convention; an f32-loaded momentum
        # trace would otherwise promote them) — so no full-width f32
        # buffer rides between update and apply, and so a lax.cond
        # accumulation-skip branch's zeros (param- or grad-width by the
        # same rule) type-match the apply branch.
        ref = params if params is not None else grads
        upd = jax.tree_util.tree_map(
            lambda u, r: u.astype(jnp.result_type(r)), upd, ref)
        return upd, store_state(new_state, dtype)

    return optax.GradientTransformationExtraArgs(init, update)


class _FusedLayout(NamedTuple):
    """Static description of how leaves pack into per-dtype buffers."""

    treedef: Any
    dtypes: tuple            # leaf dtype names, flatten order
    shapes: tuple            # leaf shapes, flatten order
    group_keys: tuple        # sorted dtype-name keys, one buffer each
    # per leaf: (group key, offset) for packed leaves, or None for
    # passthrough (large) leaves
    slots: tuple


def _nelems(shp) -> int:
    n = 1
    for d in shp:
        n *= d
    return n


def _layout_of(tree, threshold: int) -> _FusedLayout:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    dtypes = tuple(jnp.asarray(l).dtype.name for l in leaves)
    shapes = tuple(tuple(jnp.shape(l)) for l in leaves)
    offsets: dict = {}
    slots = []
    for dt, shp in zip(dtypes, shapes):
        n = _nelems(shp)
        if n >= threshold:
            slots.append(None)
            continue
        off = offsets.get(dt, 0)
        slots.append((dt, off))
        offsets[dt] = off + n
    return _FusedLayout(treedef, dtypes, shapes,
                        tuple(sorted(offsets)), tuple(slots))


def _pack(tree, layout: _FusedLayout, cast_small: bool = False):
    """Pytree → ``{"buf": {dtype_name: flat vector}, "big": [leaves]}``.
    ``cast_small`` casts packed leaves to the layout dtype (gradients of
    bf16-computed small params join the parameter-dtype buffer — standard
    master-weight mixed precision)."""
    leaves = jax.tree_util.tree_leaves(tree)
    groups: dict = {k: [] for k in layout.group_keys}
    big = []
    for i, leaf in enumerate(leaves):
        slot = layout.slots[i]
        if slot is None:
            big.append(leaf)
            continue
        dt = slot[0]
        leaf = jnp.asarray(leaf, dt) if cast_small else jnp.asarray(leaf)
        groups[dt].append(leaf.ravel())
    return {
        "buf": {k: (jnp.concatenate(v) if len(v) > 1 else v[0])
                for k, v in groups.items() if v},
        "big": big,
    }


def _unpack(packed, layout: _FusedLayout):
    """Inverse of :func:`_pack`: rebuild the original pytree."""
    leaves = []
    big = iter(packed["big"])
    for slot, shp in zip(layout.slots, layout.shapes):
        if slot is None:
            leaves.append(next(big))
            continue
        dt, off = slot
        n = _nelems(shp)
        leaves.append(packed["buf"][dt][off: off + n].reshape(shp))
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


def fuse(optimizer: optax.GradientTransformation,
         threshold_elems: int = DEFAULT_THRESHOLD_ELEMS,
         state_dtype=None) -> optax.GradientTransformationExtraArgs:
    """Wrap an elementwise optax transform so tensors smaller than
    ``threshold_elems`` update through per-dtype fused buffers (see module
    docstring); larger tensors keep their per-tensor path, preserving
    XLA's grad-producer→update fusion.

    The optimizer state becomes the wrapped transform's state over the
    packed structure (small-tensor momenta fuse too). ``update`` accepts
    ``params``; ``**extra_args`` are forwarded UNCHANGED (transforms whose
    extra args mirror the parameter tree need the unfused path).

    ``state_dtype`` applies :func:`state_storage` to the inner transform:
    the packed (and passthrough) state buffers live in the reduced dtype
    between steps while the update math stays f32.
    """
    optimizer = state_storage(optimizer, state_dtype)
    # init()'s layout is keyed by PARAM dtypes; update() must reuse it even
    # when called without params (standard optax convention) — a layout
    # recomputed from grads would group by GRAD dtype and mismatch the
    # state structure whenever the two differ (bf16 grads, f32 masters).
    # Keyed by (treedef, shapes) so one fuse()d transform serves several
    # param trees.
    layouts: dict = {}

    def _layout_key(tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return treedef, tuple(tuple(jnp.shape(l)) for l in leaves)

    def _remember(tree):
        key = _layout_key(tree)
        layout = layouts.get(key)
        if layout is None:
            layout = layouts[key] = _layout_of(tree, threshold_elems)
        return layout

    def init(params):
        return optimizer.init(_pack(params, _remember(params)))

    def update(grads, state, params=None, **extra_args):
        if params is not None:
            layout = _remember(params)
        else:
            # grads share the params' treedef/shapes, so init()'s cached
            # layout (param dtypes) is found by that key. The grads-derived
            # fallback (init ran in another process AND no params passed)
            # is deliberately NOT cached: its dtype grouping may be wrong
            # for the state, and caching it under the shared key would
            # poison later params-carrying calls.
            layout = (layouts.get(_layout_key(grads))
                      or _layout_of(grads, threshold_elems))
        # Small grads join the parameter-dtype buffers (bf16 compute
        # grads meet f32 master weights here, like the reference's fp16
        # compression decompressing into f32 before apply).
        with _phases.phase("hvd_pack"):
            pgrads = _pack(grads, layout, cast_small=True)
            pparams = None if params is None else _pack(params, layout)
        # The optax math itself is the caller's hvd_optimizer phase
        # (DistributedOptimizer.update); only the ravel and unravel
        # round it are this wrapper's own.
        pupd, new_state = optimizer.update(pgrads, state, pparams,
                                           **extra_args)
        with _phases.phase("hvd_unpack"):
            return _unpack(pupd, layout), new_state

    return optax.GradientTransformationExtraArgs(init, update)
