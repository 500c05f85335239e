"""Training-numerics observatory: gradient health, bf16 drift gauges and
the cross-rank state-consistency checker.

The observability stack before this module watched *time and bytes*
(telemetry PR 2, tracing PR 3, the performance sentinel PR 5) — nothing
watched *the numbers*. Mixed-precision training with master shards
(arxiv 2004.13336 §4) and the quantized-allreduce roadmap (EQuARX,
arxiv 2506.17615) are exactly the regimes where silent NaN/Inf
propagation, bf16 drift and cross-rank state divergence produce wrong
models that *look* fast. This module makes all three first-class,
attributed, observable events:

- **Gradient health** (:func:`note_step_health`): the compiled step
  computes global/per-bucket grad norms, nonfinite counts and a
  per-rank attribution vector *in-program*
  (:mod:`horovod_tpu.jax.numerics` — near-zero extra HBM traffic); the
  host feeds them here on the ``HVD_NUMERICS_EVERY`` cadence. A
  nonfinite step yields ONE ``nonfinite`` sentinel verdict + flight
  dump naming the step, the offending dtype bucket and the rank; under
  ``HVD_NUMERICS=halt`` the in-program guard has already skipped the
  poisoned update (params bitwise-unchanged) and :class:`NonfiniteError`
  is raised.
- **bf16 drift gauges** (:func:`note_drift` / :func:`note_update_ratio`):
  the automated version of docs/troubleshooting.md's manual drift
  ladder — periodic master↔resident max-ULP per dtype bucket on the
  sharded master path, and the update/param norm-ratio gauge for the
  masterless ``state_storage`` caveat.
- **Cross-rank consistency digest** (:func:`check_consistency`): at
  control-plane points every process digests its parameter buckets
  (crc32 over the raw bytes + an f64 sum + a nonfinite count), the
  digests are allgathered, and a mismatch yields an attributed
  ``diverged`` verdict naming the deviating rank(s) and bucket on EVERY
  process — the detection instrument elastic worlds (ROADMAP item 3)
  and quantized allreduce (item 1) will both stand on.

Engines: both engines call :func:`engine_note_submit` /
:func:`engine_check_result` on their python submit/synchronize
boundaries — a nonfinite reduced result triggers a one-shot cross-rank
attribution exchange (an eager allgather of each process's local
nonfinite count at submit), so every survivor's verdict names the
poisoning rank. Like ``HVD_CONSISTENCY_CHECKS``, the exchange assumes
SPMD-symmetric synchronize order across processes (the standard
collective-call contract).

Knobs: ``HVD_NUMERICS=off|warn|halt`` (default **warn**), and
``HVD_NUMERICS_EVERY`` (host check cadence in steps, default 50; the
halt policy checks every step). Stdlib + numpy only on the observe
path; jax is imported only where a collective actually runs.

Surfaces: ``hvd.numerics_report()``, the ``hvd_numerics_*`` metric
family in every telemetry exposition (file, ``/metrics``,
``utils.stats --json``), ``/healthz`` (degrades on a recent
``nonfinite``/``diverged`` verdict), ``python -m
horovod_tpu.utils.numerics <file|http://...>``.
"""

from __future__ import annotations

import logging
import os
import threading
import zlib
from typing import Dict, List, Optional

import numpy as np

from horovod_tpu.core import sentinel as _sentinel
from horovod_tpu.core import telemetry as tele

LOG = logging.getLogger("horovod_tpu.numerics")

_POLICIES = ("off", "warn", "halt")


class NonfiniteError(RuntimeError):
    """Raised under ``HVD_NUMERICS=halt`` when a nonfinite gradient (or
    reduced engine result) is detected. The in-program guard has already
    kept the poisoned update from being applied."""


def policy() -> str:
    """The ``HVD_NUMERICS`` policy: ``off`` (no instrumentation — the
    compiled step lowers to the identical HLO as pre-numerics builds),
    ``warn`` (observe + verdict + dump) or ``halt`` (additionally skip
    the poisoned update in-program and raise). Default ``warn``;
    unknown spellings are treated as ``warn`` with one log line, and
    ``0``/``false`` read as ``off``."""
    v = os.environ.get("HVD_NUMERICS", "warn").strip().lower()
    if v in ("0", "false", "no"):
        return "off"
    if v in ("1", "true", "on"):
        return "warn"
    if v not in _POLICIES:
        LOG.warning("HVD_NUMERICS=%r is not off|warn|halt; treating as "
                    "'warn'", v)
        return "warn"
    return v


def enabled() -> bool:
    return policy() != "off"


def check_every() -> int:
    """Host-side check cadence in steps (``HVD_NUMERICS_EVERY``, default
    50). The halt policy always checks every step — a detection delayed
    by the cadence could not raise before the NEXT poisoned update."""
    try:
        return max(1, int(os.environ.get("HVD_NUMERICS_EVERY", "") or 50))
    except ValueError:
        return 50


# ---------------------------------------------------------------------------
# State: fire-once latches + last reports (one per process)
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_fired: Dict[str, dict] = {}      # verdict kind -> first verdict
_last_drift: Optional[dict] = None
_last_consistency: Optional[dict] = None
_engine_submit_nf: Dict[str, int] = {}  # tensor name -> local nf count
_ENGINE_SUBMIT_MAX = 1024
# One-shot latch for the engine attribution allgather, SEPARATE from
# the _fired verdict latch: _fired can be set asymmetrically across
# processes (a process-local Trainer verdict), and a collective gated
# on an asymmetric flag is a distributed hang. This flag flips only
# inside engine_check_result, whose entry is SPMD-symmetric (identical
# reduced results, identical synchronize order — the documented engine
# contract), so every process takes the exchange branch together.
_engine_attr_done = False


def reset():
    """Drop the latches and reports (tests only)."""
    global _last_drift, _last_consistency, _engine_attr_done
    with _lock:
        _fired.clear()
        _engine_submit_nf.clear()
        _last_drift = None
        _last_consistency = None
        _engine_attr_done = False


def _fire(kind: str, info: dict) -> dict:
    """One attributed verdict + flight dump per verdict kind per process
    (the sentinel's dump layer additionally rate-limits repeats of the
    same reason); later events of the same kind only count."""
    with _lock:
        first = kind not in _fired
        if first:
            _fired[kind] = info
    tele.REGISTRY.counter(f"numerics.{kind}.events").inc()
    if first:
        return _sentinel.note_numerics(kind, info)
    return dict(info, verdict=kind, dump=None, suppressed=True)


# ---------------------------------------------------------------------------
# Gradient health intake (the compiled path lands here via the Trainer)
# ---------------------------------------------------------------------------


def note_step_health(health: dict, step: Optional[int] = None,
                     origin: str = "trainer"):
    """One step's in-program health stats, already fetched to host
    (plain numbers / 0-d numpy). Feeds the telemetry rings and gauges;
    fires the ``nonfinite`` verdict (first offender: step, bucket, rank)
    and — under the ``halt`` policy — raises :class:`NonfiniteError`
    AFTER the dump landed. Never mutates training state: the in-program
    guard already kept the update from applying."""
    if not health:
        return None
    tele.REGISTRY.counter("numerics.steps.checked").inc()
    gn = health.get("grad_norm")
    if gn is not None:
        gn = float(gn)
        tele.REGISTRY.ring("numerics.grad_norm").push(gn)
    buckets = health.get("buckets") or {}
    for k, b in buckets.items():
        tele.REGISTRY.gauge(f"numerics.grad_norm.{k}").set(
            float(b["norm"]))
    if "update_norm" in health and "param_norm" in health:
        note_update_ratio(float(health["update_norm"]),
                          float(health["param_norm"]))
    nf_total = int(health.get("nonfinite") or 0)
    bad_buckets = {k: int(b["nonfinite"]) for k, b in buckets.items()
                   if int(b["nonfinite"])}
    if not nf_total and not bad_buckets:
        return None
    tele.REGISTRY.counter("numerics.nonfinite.steps").inc()
    tele.REGISTRY.counter("numerics.nonfinite.values").inc(
        max(nf_total, sum(bad_buckets.values())))
    ranks: List[int] = []
    per_rank = health.get("per_rank_nonfinite")
    if per_rank is not None:
        arr = np.asarray(per_rank).reshape(-1)
        ranks = [int(r) for r in np.nonzero(arr)[0]]
    info = {
        "origin": origin,
        "step": int(step) if step is not None else None,
        "grad_norm": gn,
        "nonfinite": nf_total,
        "buckets": bad_buckets,
        "ranks": ranks or None,
    }
    verdict = _fire("nonfinite", info)
    if policy() == "halt":
        raise NonfiniteError(
            f"nonfinite gradients at step {info['step']}: "
            f"{nf_total} value(s) in bucket(s) "
            f"{sorted(bad_buckets) or '?'}"
            + (f" from rank(s) {ranks}" if ranks else "")
            + " — the poisoned update was NOT applied "
              "(HVD_NUMERICS=halt)")
    return verdict


# ---------------------------------------------------------------------------
# Drift gauges (bf16 resident state — the automated troubleshooting ladder)
# ---------------------------------------------------------------------------


def note_drift(ulp_by_bucket: Dict[str, int], step: Optional[int] = None):
    """Periodic master↔resident divergence, as max ULP per dtype bucket
    (:func:`horovod_tpu.jax.sharded.drift_ulp` computes it). The
    re-anchored sharded path should read ≤1; growth means the policy is
    not applied where you think (docs/troubleshooting.md)."""
    global _last_drift
    tele.REGISTRY.counter("numerics.drift.checks").inc()
    for k, u in ulp_by_bucket.items():
        tele.REGISTRY.gauge(f"numerics.drift_ulp.{k}").set(int(u))
    with _lock:
        _last_drift = {"step": step,
                       "ulp": {k: int(u) for k, u in
                               ulp_by_bucket.items()}}


def note_update_ratio(update_norm: float, param_norm: float):
    """The masterless-path gauge (``fused.state_storage`` caveat): the
    ||update||/||params|| ratio. Sustained ratios below ~1 bf16 ulp
    (~0.4 %) of the weights mean updates are being rounded away —
    exactly the late-training drift regime the troubleshooting ladder
    diagnoses by hand."""
    tele.REGISTRY.gauge("numerics.update_norm").set(update_norm)
    tele.REGISTRY.gauge("numerics.param_norm").set(param_norm)
    if param_norm > 0:
        tele.REGISTRY.gauge("numerics.update_ratio").set(
            update_norm / param_norm)


# ---------------------------------------------------------------------------
# Cross-rank consistency digest
# ---------------------------------------------------------------------------


#: Entries per bucket digest row: [crc_hi16, crc_lo16, sum, nonfinite].
#: The crc32 ships as two 16-bit halves because the wire is f32 (the
#: eager allgather runs without x64): a whole 32-bit crc would round to
#: ~24 bits of mantissa and a near-collision divergence could vanish in
#: transit. 16-bit halves are exact in f32 at any value.
DIGEST_WIDTH = 4


def params_digest(tree) -> Dict[str, np.ndarray]:
    """Per-dtype-bucket digest of a parameter pytree: ``[crc32 high
    half, crc32 low half, sum, nonfinite count]``. The crc makes ANY
    bitwise difference visible; the sum/count give a human a direction.
    Host math only — identical inputs digest identically on every
    process."""
    from horovod_tpu.ops import collectives as _C

    buckets: Dict[str, List[np.ndarray]] = {}
    import jax as _jax

    for leaf in _jax.tree_util.tree_leaves(tree):
        arr = _C.fetch(leaf) if hasattr(leaf, "dtype") else np.asarray(leaf)
        buckets.setdefault(np.asarray(arr).dtype.name, []).append(
            np.asarray(arr))
    out = {}
    for k in sorted(buckets):
        crc = 0
        total = 0.0
        nf = 0
        for a in buckets[k]:
            crc = zlib.crc32(np.ascontiguousarray(a).tobytes(), crc)
            af = a.astype(np.float64, copy=False) \
                if np.issubdtype(a.dtype, np.floating) else a
            if np.issubdtype(a.dtype, np.floating):
                fin = np.isfinite(af)
                total += float(af[fin].sum())
                nf += int(a.size - fin.sum())
            else:
                total += float(np.asarray(af, np.float64).sum())
        # The f32-rounded sum stays deterministic (identical f64 in →
        # identical f32 out) — it is the human-direction field; the crc
        # halves are the exact divergence detector.
        out[k] = np.asarray([float(crc >> 16), float(crc & 0xFFFF),
                             np.float32(total), float(nf)], np.float64)
    return out


def compare_digests(gathered: np.ndarray, bucket_names: List[str],
                    local_size: int) -> dict:
    """Pure comparison (unit-testable without a world): ``gathered`` is
    the (world, nbuckets, DIGEST_WIDTH) matrix of every chip's process
    digest. A STRICT majority digest wins and the deviating chips are
    mapped to controller processes by the contiguous local-block rule.
    Without a strict majority (the 2-process 4-vs-4 tie: each process's
    digest is replicated across its local chips, so a two-controller
    disagreement can never out-vote itself) the divergence is real but
    unattributable by vote — EVERY rank is reported and the report is
    marked ``ambiguous`` rather than letting dict-insertion order crown
    rank 0's digest and blame the possibly-healthy other side. Identical
    input → identical report on every process."""
    world = gathered.shape[0]
    mismatch: Dict[str, List[int]] = {}
    ambiguous = False
    for bi, name in enumerate(bucket_names):
        rows = [tuple(gathered[r, bi]) for r in range(world)]
        counts: Dict[tuple, int] = {}
        for t in rows:
            counts[t] = counts.get(t, 0) + 1
        if len(counts) == 1:
            continue
        best = max(counts.values())
        leaders = [t for t, c in counts.items() if c == best]
        if len(leaders) == 1 and best * 2 > world:
            majority = leaders[0]
            mismatch[name] = [r for r, t in enumerate(rows)
                              if t != majority]
        else:
            mismatch[name] = list(range(world))
            ambiguous = True
    report = {"ok": not mismatch, "buckets": list(bucket_names),
              "world": world}
    if mismatch:
        ranks = sorted({r for rs in mismatch.values() for r in rs})
        report["mismatch"] = {k: v for k, v in mismatch.items()}
        report["ranks"] = ranks
        report["processes"] = sorted({r // max(1, local_size)
                                      for r in ranks})
        if ambiguous:
            report["ambiguous"] = True
    return report


def check_consistency(tree, tag: str = "params",
                      step: Optional[int] = None) -> dict:
    """Allreduce-compare a cheap per-bucket parameter digest across the
    world (an eager allgather — call from a control-plane point, in
    lockstep on every process). A mismatch yields an attributed
    ``diverged`` verdict + flight dump on EVERY process, naming the
    deviating rank(s) and bucket. Returns the report dict."""
    global _last_consistency
    import jax.numpy as jnp

    from horovod_tpu.common import topology as _topo
    from horovod_tpu.ops import collectives as _C

    st = _topo._require_init()
    tele.REGISTRY.counter("numerics.consistency.checks").inc()
    digest = params_digest(tree)
    names = sorted(digest)
    local = np.stack([digest[k] for k in names]) if names else \
        np.zeros((0, DIGEST_WIDTH), np.float64)
    if st.size == 1 or not names:
        report = {"ok": True, "buckets": names, "world": st.size}
    else:
        gathered = np.asarray(_C.allgather(
            jnp.asarray(local.reshape(1, -1))))
        gathered = gathered.reshape(st.size, len(names), DIGEST_WIDTH)
        report = compare_digests(gathered, names, st.local_size)
    report["tag"] = tag
    if step is not None:
        report["step"] = step
    with _lock:
        _last_consistency = report
    if not report["ok"]:
        tele.REGISTRY.counter("numerics.consistency.mismatches").inc()
        info = {"origin": "numerics.consistency", "tag": tag,
                "step": step,
                "buckets": sorted(report["mismatch"]),
                "ranks": report["ranks"],
                "processes": report["processes"]}
        _fire("diverged", info)
    return report


# ---------------------------------------------------------------------------
# Engine hooks (both engines' python submit/synchronize boundaries)
# ---------------------------------------------------------------------------


def np_nonfinite(tensor) -> int:
    try:
        t = np.asarray(tensor)
        if t.dtype.kind == "f":  # the common case, sans issubdtype cost
            return int((~np.isfinite(t)).sum())
        if not np.issubdtype(t.dtype, np.floating):
            try:  # ml_dtypes (bfloat16) are floating but not np.floating
                t = t.astype(np.float32)
            except (TypeError, ValueError):
                return 0
        return int((~np.isfinite(t)).sum())
    except Exception:  # pragma: no cover - defensive
        return 0


def engine_note_submit(name: str, tensor):
    """Called by both engines at ``*_async`` submit (on the snapshot):
    records this process's local nonfinite count per tensor name — the
    attribution side of :func:`engine_check_result`'s exchange."""
    if not enabled():
        return
    nf = np_nonfinite(tensor)
    if nf:
        tele.REGISTRY.counter("numerics.engine.nonfinite_submits").inc()
    with _lock:
        while len(_engine_submit_nf) >= _ENGINE_SUBMIT_MAX:
            _engine_submit_nf.pop(next(iter(_engine_submit_nf)))
        _engine_submit_nf[name] = nf


def engine_note_submit_batch(names, tensors):
    """The batched-submit twin of :func:`engine_note_submit` — identical
    per-tensor semantics (same counter, same attribution dict), but the
    policy/env gate, counter feed and latch lock are paid ONCE per
    batch, not once per member: a 10k-member ``submit_n`` must not
    spend more time in instrumentation wrappers than in the submit
    itself (measured: the per-call form cost ~22 us/tensor, most of it
    env reads and lock churn)."""
    if not enabled():
        return
    counts = [np_nonfinite(t) for t in tensors]
    bad = sum(1 for nf in counts if nf)
    if bad:
        tele.REGISTRY.counter("numerics.engine.nonfinite_submits").inc(bad)
    with _lock:
        for name, nf in zip(names, counts):
            while len(_engine_submit_nf) >= _ENGINE_SUBMIT_MAX:
                _engine_submit_nf.pop(next(iter(_engine_submit_nf)))
            _engine_submit_nf[name] = nf


def engine_check_result(name: str, result):
    """Called by both engines in ``synchronize``: a nonfinite reduced
    result fires the one-shot attribution exchange — every process
    allgathers its local-at-submit nonfinite count, so every survivor's
    ``nonfinite`` verdict names the poisoning process. Raises
    :class:`NonfiniteError` under the halt policy. Identical counter
    names and verdict shape on both engines (this IS the shared code)."""
    if not enabled():
        return
    nf = np_nonfinite(result)
    if not nf:
        return
    global _engine_attr_done
    tele.REGISTRY.counter("numerics.engine.nonfinite_results").inc()
    with _lock:
        local = _engine_submit_nf.get(name, 0)
        first_exchange = not _engine_attr_done
        _engine_attr_done = True
    processes = None
    if first_exchange:
        # One-shot exchange, gated on ITS OWN latch (not _fired, which a
        # process-local Trainer verdict can set asymmetrically — see the
        # latch comment above): all processes synchronize the same
        # reduced (identically nonfinite) tensor, so all enter here
        # together — the same SPMD-symmetry contract
        # HVD_CONSISTENCY_CHECKS documents. Best-effort: a world where
        # the eager path is unavailable still gets the local-knowledge
        # verdict.
        try:
            import jax.numpy as jnp

            from horovod_tpu.common import topology as _topo
            from horovod_tpu.ops import collectives as _C

            st = _topo._require_init()
            flags = np.asarray(_C.allgather(
                jnp.asarray([[np.int32(local)]])))
            flags = flags.reshape(-1)
            processes = sorted({int(r) // max(1, st.local_size)
                                for r in np.nonzero(flags)[0]})
        except Exception as exc:  # pragma: no cover - defensive
            LOG.warning("nonfinite attribution exchange unavailable: %s",
                        exc)
    info = {"origin": "engine", "tensor": name, "nonfinite": nf,
            "local_nonfinite_at_submit": local,
            "processes": processes}
    _fire("nonfinite", info)
    if policy() == "halt":
        raise NonfiniteError(
            f"nonfinite reduced result for '{name}' ({nf} value(s))"
            + (f" from process(es) {processes}" if processes else "")
            + " (HVD_NUMERICS=halt)")


def note_eager_nonfinite(op: str, count: int):
    """Eager-collective input carried nonfinite values (the collectives
    layer feeds this when the policy is on) — a counter, not a verdict:
    metric averaging has its own masking (utils/metrics.py)."""
    if count:
        tele.REGISTRY.counter(f"numerics.eager.{op}.nonfinite").inc(count)


# ---------------------------------------------------------------------------
# Report surfaces
# ---------------------------------------------------------------------------


def report() -> dict:
    """The ``hvd.numerics_report()`` surface: policy + the current state
    of every numerics gauge/counter family + the last drift/consistency
    reports and first verdicts."""
    flat = tele.REGISTRY.flat()
    num = {k: v for k, v in flat.items() if k.startswith("numerics.")}
    with _lock:
        fired = {k: dict(v) for k, v in _fired.items()}
        drift = dict(_last_drift) if _last_drift else None
        consistency = dict(_last_consistency) if _last_consistency \
            else None
    return {
        "policy": policy(),
        "check_every": check_every(),
        "metrics": num,
        "verdicts": fired or None,
        "drift": drift,
        "consistency": consistency,
    }


def compact() -> dict:
    """Small summary for embedding in one JSON object (nulls when
    nothing was observed)."""
    flat = tele.REGISTRY.flat()
    ring = flat.get("numerics.grad_norm") or {}
    with _lock:
        consistency_ok = (None if _last_consistency is None
                          else bool(_last_consistency["ok"]))
        fired = sorted(_fired) or None
    return {
        "policy": policy(),
        "steps_checked": flat.get("numerics.steps.checked") or None,
        "nonfinite_steps": flat.get("numerics.nonfinite.steps") or None,
        "grad_norm_last": ring.get("last"),
        "consistency_ok": consistency_ok,
        "verdicts": fired,
    }


def summary() -> dict:
    """The sentinel /healthz payload's ``numerics`` section."""
    with _lock:
        return {
            "policy": policy(),
            "verdicts": sorted(_fired) or None,
            "drift": dict(_last_drift) if _last_drift else None,
            "consistency_ok": (None if _last_consistency is None
                               else bool(_last_consistency["ok"])),
        }
