"""Phase names inside the compiled step (``horovod_tpu/common/phases.py``):
every name of the vocabulary reaches ``metadata.op_name`` of a tiny
``DistributedOptimizer`` step compiled on the 8-device CPU mesh, the
collectives carry ``hvd_allreduce``, and the names are free: with
``phases.phase`` a null context and the stamp off, the compiled HLO has
the same opcodes in the same number. And the trap the stamp closes: jax's
persistent compile cache keys on the program without its debug info, so
names alone would be served an older executable's names."""

import collections
import contextlib
import inspect
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd_jax
from horovod_tpu.common import phases

_OPCODE = re.compile(r"^\s+(?:ROOT )?%?[\w.\-]+ = (?:\([^=]*?\)|\S+) "
                     r"([\w\-]+)\(", re.M)
_COLLECTIVE = re.compile(
    r"^.* (?:all-reduce|all-gather|reduce-scatter|all-to-all)"
    r"(?:-start)?\(.*$", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')

VARIANTS = {
    "fused": dict(fused_update=True),
    "sharded": dict(sharded_update=True),
    "int8": dict(fused_update=True, compression="int8"),
}


def _compiled_text(variant: str) -> str:
    """One adam step over a tree with a leaf above the fused update's
    packing threshold and two below it; a fresh function each call, so
    nothing traced earlier is reused."""
    params = {"big": jnp.ones((64, 64)), "small": jnp.ones((32,)),
              "bias": jnp.zeros((8,))}
    opt = hvd_jax.DistributedOptimizer(optax.adam(1e-3), **VARIANTS[variant])
    state = opt.init(params)
    o_spec = (hvd_jax.sharded_state_specs(state)
              if variant == "sharded" else P())

    def loss_fn(p, x):
        return jnp.mean((x @ p["big"]) ** 2) + jnp.sum(p["small"]) \
            + jnp.sum(p["bias"])

    @hvd_jax.jit(in_specs=(P(), o_spec, P(hvd_jax.HVD_AXIS)),
                 out_specs=(P(), o_spec, P()))
    def train_step(p, s, x):
        loss, grads = jax.value_and_grad(loss_fn)(p, x)
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), s, hvd_jax.allreduce(loss)

    x = jnp.ones((16, 64))
    return train_step.lower(params, state, x).compile().as_text()


def _stacks(text):
    return [name.split("/") for name in _OP_NAME.findall(text)]


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_every_phase_is_named_in_the_compiled_step(hvd, variant,
                                                   monkeypatch):
    # halt makes the statistics live (they guard the update); the
    # default policy leaves them dead unless collected.
    monkeypatch.setenv("HVD_NUMERICS", "halt")
    text = _compiled_text(variant)
    stacks = _stacks(text)
    for name in phases.PHASES:
        assert any(name in stack for stack in stacks), name
    collectives = _COLLECTIVE.findall(text)
    assert collectives
    exchange = 0
    for line in collectives:
        stack = _OP_NAME.search(line).group(1).split("/")
        # The per-rank nonfinite vector is gathered under hvd_numerics;
        # every other collective of the step is the exchange.
        assert "hvd_allreduce" in stack or "hvd_numerics" in stack, line
        exchange += "hvd_allreduce" in stack
    assert exchange
    assert f'hvd_phases="{phases.VOCABULARY_VERSION}"' in text


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_names_are_free(hvd, variant, monkeypatch):
    named = _compiled_text(variant)
    monkeypatch.setattr(phases, "phase",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(phases, "stamped", lambda fn: fn)
    bare = _compiled_text(variant)
    assert not any(name in stack for stack in _stacks(bare)
                   for name in phases.PHASES)
    assert "hvd_phases" not in bare
    assert collections.Counter(_OPCODE.findall(named)) == \
        collections.Counter(_OPCODE.findall(bare))


def test_an_unknown_phase_is_refused():
    outer = "hvd_update"  # the benchmark's own wrapper, not a phase
    with pytest.raises(ValueError, match="unknown phase 'hvd_update'"):
        phases.phase(outer)
    for name in phases.PHASES:
        with phases.phase(name):
            pass


def test_kernel_names_keep_what_trace_readers_match():
    # benchmark/metrics/flash_ms_per_step.py goes by these substrings of
    # the custom-call's identifier, which name= replaces.
    fwd, dq, dkv, fused = phases.KERNELS[:4]
    assert "_fwd_bhsd" in fwd and "_bwd_bhsd" in dq and "_bwd_bhsd" in dkv
    # The fused backward is read as the dK/dV kernel, never as the dQ one
    # (benchmark/harness/phases.py KERNELS: first pattern that matches).
    assert dkv in fused and dq not in fused
    from horovod_tpu.ops import chunked_loss, flash_attention

    source = inspect.getsource(flash_attention) + inspect.getsource(
        chunked_loss)
    assert sorted(re.findall(r'\bname="(\w+)"', source)) == \
        sorted(phases.KERNELS)


_CACHE_TRAP = """
import contextlib, json, os, sys
import jax
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
import horovod_tpu as hvd
from horovod_tpu.common import phases
import test_phases
hvd.init()
named, stamped = phases.phase, phases.stamped
def build(names, stamp):
    phases.phase = named if names else (lambda n: contextlib.nullcontext())
    phases.stamped = stamped if stamp else (lambda f: f)
    return "hvd_pack" in test_phases._compiled_text("fused")
print(json.dumps({"before the names": build(False, False),
                  "names alone": build(True, False),
                  "names and stamp": build(True, True)}))
"""


def test_the_stamp_keeps_a_compile_cache_from_serving_old_names(tmp_path):
    """One cache, three programs that differ in nothing the cache key
    sees but the stamp: the step before the names, with the names, with
    names and stamp. Should "names alone" ever read True, jax keys on the
    names itself and ``phases.stamped`` can go."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [os.path.dirname(here), here]),
        XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_TRAP, str(tmp_path / "cache")],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.splitlines()[-1]) == {
        "before the names": False, "names alone": False,
        "names and stamp": True}
