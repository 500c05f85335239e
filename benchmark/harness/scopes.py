"""Device time by the model's own scopes (``horovod_tpu.common.phases.
MODEL_SCOPES``): seconds of the ops whose ``op_name`` stack holds one of
a set of names.

Beside ``phases.py``, whose vocabulary and tables stay the framework's:
this reader gives an event no phase, it only asks whether the event
belongs to one of the names a metric wants. The join is the same: an
event on ``XLA Ops`` to the compiled step's text (``System.hlo_text``)
by identifier (``phases.parse``, ``xtrace.identifier``). An op belongs to
the innermost wanted name of, first that applies: the ``dot`` it is or
holds (the product sets a fusion's time); its own ``op_name``; what most
of a fusion's named instructions say. Ops inside a ``while`` body are
events of their own and are read like any other; the ``while`` itself is
a wrapper and is not counted. A program whose text holds none of the
wanted names (one from before them) reads ``None``, never 0.
"""

from __future__ import annotations

import collections
import json
from typing import Dict, List, Optional, Sequence

from benchmark.harness import phases, xtrace

_MATMULS = frozenset({"dot", "convolution"})


def _named(op_name: str, wanted) -> Optional[str]:
    for part in reversed(op_name.split("/")):
        if part in wanted:
            return part
    return None


def scope_of(key: str, module: phases.Module, wanted) -> Optional[str]:
    """The name of ``wanted`` that instruction ``key`` belongs to."""
    instr = module.instructions.get(key)
    if instr is None:
        return None
    inner = [module.instructions[n]
             for n in module.computations.get(instr.calls, ())]
    matmul = next((i for i in [instr] + inner if i.opcode in _MATMULS), None)
    if matmul is not None:
        return _named(matmul.op_name, wanted)
    own = _named(instr.op_name, wanted)
    if own is not None or not inner:
        return own
    votes = collections.Counter(
        _named(i.op_name, wanted) for i in inner if i.op_name)
    if not votes:
        return None
    return votes.most_common(1)[0][0]


def read(hlo_text: str, capture: xtrace.Capture, window,
         wanted: Sequence[str], module: Optional[phases.Module] = None
         ) -> Optional[List[Dict[str, float]]]:
    """Per device, seconds of ``window`` by name of ``wanted``; ``None``
    where the text holds none of them. ``module``: the text, parsed
    already."""
    wanted = frozenset(wanted)
    if not any(name in hlo_text for name in wanted):
        return None
    module = module or phases.parse(hlo_text)
    verdicts: Dict[str, Optional[str]] = {}
    lo, hi = window
    out = []
    for dev in capture.devices:
        seconds: Dict[str, float] = collections.defaultdict(float)
        for name, start, end in dev.ops():
            overlap = (min(end, hi) - max(start, lo)) / 1e9
            if overlap <= 0:
                continue
            if name not in verdicts:
                verdicts[name] = scope_of(xtrace.identifier(name), module,
                                          wanted)
            if verdicts[name] is not None:
                seconds[verdicts[name]] += overlap
        out.append(dict(seconds))
    return out


def per_step_ms(context, wanted: Sequence[str]) -> Optional[float]:
    """Device milliseconds a step spends under ``wanted``, mean over the
    devices; ``None`` for a program without those names."""
    text = context.system.hlo_text
    module = vars(context).get("_scopes_module")
    if module is None:  # parsed once for all the metrics of a run
        module = vars(context)["_scopes_module"] = phases.parse(text)
    got = read(text, context.capture, context.window, wanted, module)
    if got is None:
        return None
    if len(wanted) > 1:  # an earlier line of stdout, as the phases' is
        print(json.dumps({"phase": "scopes", "ms_per_step": {
            name: context.per_step_ms([d.get(name, 0.0) for d in got])
            for name in wanted}}), flush=True)
    return context.per_step_ms([sum(d.values()) for d in got])


def routing_counters(context) -> Optional[dict]:
    """The family's routing counters of the last step, as numpy arrays:
    ``expert_kept`` (sparse layers, held experts) and ``expert_elsewhere``
    (sparse layers,). ``None`` where the extra state carries none."""
    import numpy as np

    extra = context.system.state[1]
    if not isinstance(extra, dict) or "expert_kept" not in extra:
        return None
    return {k: np.asarray(extra[k]) for k in ("expert_kept",
                                              "expert_elsewhere")}
