"""Telemetry stats CLI — the query tool over the unified registry.

    python -m horovod_tpu.utils.stats <target> [--json] [--watch N]

``target`` is one of:

- a Prometheus-style text file written by ``HVD_TELEMETRY_FILE`` (see
  :mod:`horovod_tpu.core.telemetry`) — parsed and pretty-printed
  (``--watch N`` re-reads every N seconds, the poor-man's dashboard);
- an ``http://host:port`` (or full ``.../metrics``) URL served by
  ``HVD_TELEMETRY_PORT`` (:mod:`horovod_tpu.core.telemetry_http`) —
  fetched and rendered exactly like the file (``--watch`` re-fetches);
- an XLA profiler capture directory (``utils.profiler``'s, or the
  sentinel's auto-capture) — the
  machine-readable HBM attribution (:func:`horovod_tpu.utils.xplane.
  hbm_json`, the same data ``xplane --hbm --json`` emits), so bench
  tooling never re-parses the human table;
- ``live`` — snapshot of the *current process's* registry (only useful
  from code/REPL in the process doing the work; cross-process use goes
  through the exposition file or the HTTP endpoint).

``--json`` emits ONE envelope shape regardless of source — ``{"source",
"target", "samples": [{"name", "labels", "value"}, ...]}`` — so a
dashboard script written against a file keeps working pointed at a live
``http://`` rank or a capture dir (xplane figures flatten into
``xplane_*`` samples with the op class as a label).

``--fleet <target>`` switches to the live world console: the merged
cross-rank rollup (:mod:`horovod_tpu.core.fleet`) rendered as a
step-time sparkline, per-op latency quantiles (p50/p99/p999 merged
exactly across ranks), deadline/cancel/ring-full counts, and a
per-rank heatmap with last-beat ages and STALE/DEAD marking. The
target is the rank-0 HTTP endpoint (``/fleet`` picked automatically),
a fleet KV directory (``HVD_FLEET_DIR`` — readable with no live
process), or a saved report JSON; ``--watch N`` redraws.

``--doctor <target>`` renders the hang doctor's attributed verdict
(:mod:`horovod_tpu.core.doctor`): the target is a live rank's HTTP
endpoint (``/doctor`` picked automatically — triggers an on-demand
diagnosis), a flight-dump directory (offline diagnosis over the
embedded inspect tables — works on a dead world), or a saved verdict /
single dump JSON file."""

from __future__ import annotations

import json
import os
import re
import time
from typing import Dict, List, Tuple

_SAMPLE_RE = re.compile(
    r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{([^}]*)\})?\s+(-?[0-9.eE+\-infa]+)$")

# The hang doctor's classification vocabulary as this consumer renders
# it, in attribution-priority order. Machine-diffed against
# ``VERDICT_KINDS`` in core/doctor.py by hvdcheck rule ``parity-doctor``
# — a kind renamed on either side breaks the other's rendering, so the
# analysis names the skew instead of a dashboard showing "unknown".
_DOCTOR_KINDS = (
    "dead_peer",
    "draining",
    "overload",
    "missing_submitter",
    "metadata_mismatch",
    "slow_executor",
    "kv_degraded",
)


def parse_prometheus(text: str) -> List[Tuple[str, Dict[str, str], float]]:
    """Parse exposition text into (name, labels, value) samples. Ignores
    comments/TYPE lines and anything unparseable (forward compatible)."""
    out = []
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            continue
        name, labels_raw, value = m.groups()
        labels: Dict[str, str] = {}
        if labels_raw:
            for part in labels_raw.split(","):
                if "=" in part:
                    k, v = part.split("=", 1)
                    labels[k.strip()] = v.strip().strip('"')
        try:
            out.append((name, labels, float(value)))
        except ValueError:
            continue
    return out


def render(samples: List[Tuple[str, Dict[str, str], float]]) -> str:
    """Human table of parsed samples, histogram buckets folded to a
    count+mean line (the full distribution stays in the file)."""
    if not samples:
        return "no samples"
    rows = []
    hist: Dict[str, Dict[str, float]] = {}
    for name, labels, value in samples:
        if name.endswith("_bucket"):
            continue  # summarized via _sum/_count below
        if name.endswith(("_sum", "_count")):
            base = name.rsplit("_", 1)[0]
            hist.setdefault(base, {})[name.rsplit("_", 1)[1]] = value
            continue
        label = name
        if labels:
            label += "{" + ",".join(f"{k}={v}"
                                    for k, v in sorted(labels.items())) + "}"
        rows.append((label, f"{value:g}"))
    for base, parts in sorted(hist.items()):
        n = parts.get("count", 0)
        if "sum" not in parts:
            # Not a histogram pair: a Ring exports <name>_count (+ _last/
            # _mean gauges printed above) with no _sum — folding it into
            # a fake "mean=0" row would contradict the real mean beside
            # it.
            rows.append((base + "_count", f"{n:g}"))
            continue
        mean = parts["sum"] / n if n else 0.0
        rows.append((base, f"n={n:g} mean={mean:.6g}"))
    width = max(len(r[0]) for r in rows)
    return "\n".join(f"{label:{width}s} {value:>18s}"
                     for label, value in sorted(rows))


_SPARK = "▁▂▃▄▅▆▇█"


def sparkline(values: List[float], width: int = 40) -> str:
    """Unicode block sparkline of the last ``width`` values (the
    step-time strip at the top of the fleet console)."""
    vals = [v for v in values[-width:] if isinstance(v, (int, float))]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    span = hi - lo
    if span <= 0:
        return _SPARK[0] * len(vals)
    return "".join(
        _SPARK[min(len(_SPARK) - 1,
                   int((v - lo) / span * len(_SPARK)))] for v in vals)


def render_fleet(report: dict) -> str:
    """Human console of a fleet rollup (``hvd.fleet_report()`` /
    ``GET /fleet`` / ``core.fleet.report_from_dir``): world line,
    step-time sparkline, per-op latency quantiles, deadline/cancel
    counts, and the per-rank heatmap with last-beat ages and
    STALE/DEAD marking."""
    lines: List[str] = []
    marks = []
    if report.get("stale"):
        marks.append(f"STALE={report['stale']}")
    if report.get("dead"):
        marks.append(f"DEAD={report['dead']}")
    lines.append(
        f"world: size={report.get('size', 0)} "
        f"epoch={report.get('epoch', 0)} "
        f"generation={report.get('generation', 0)}"
        + (" " + " ".join(marks) if marks else ""))
    doc = report.get("doctor")
    if doc and doc.get("kind"):
        # The hang doctor's blamed-tensor line: verdict kind + the
        # tensor/ranks it attributed (core/doctor.py, folded through
        # the fleet snapshots).
        lines.append(
            f"doctor: {doc['kind']}"
            + (f" tensor='{doc['tensor']}'" if doc.get("tensor") else "")
            + (f" rank(s) {doc['ranks']}" if doc.get("ranks") else ""))
    step = report.get("step") or {}
    strip = sparkline(step.get("sparkline") or [])
    if strip:
        last = (step.get("sparkline") or [None])[-1]
        lines.append(f"step_s: {strip}  last={last:.4g}"
                     if isinstance(last, (int, float))
                     else f"step_s: {strip}")
    ops = report.get("ops") or {}
    if ops:
        lines.append("op          count     p50_us      p99_us     p999_us")
        for op, q in sorted(ops.items()):
            lines.append(
                f"{op:<10s} {q.get('count', 0):>6} "
                f"{_fmt_us(q.get('p50_us')):>10s} "
                f"{_fmt_us(q.get('p99_us')):>11s} "
                f"{_fmt_us(q.get('p999_us')):>11s}")
    phases = report.get("phases") or {}
    if phases:
        lines.append("phase: " + "  ".join(
            f"{name} p50={_fmt_us(q.get('p50_us'))}us"
            for name, q in sorted(phases.items())))
    classes = report.get("classes") or {}
    if classes:
        lines.append("class: " + "  ".join(
            f"{cls} p50={_fmt_us(q.get('p50_us'))}us "
            f"p99={_fmt_us(q.get('p99_us'))}us"
            for cls, q in sorted(classes.items())))
    dl = report.get("deadline") or {}
    lines.append(
        f"deadline: exceeded={dl.get('exceeded', 0):g} "
        f"cancelled={dl.get('cancelled', 0):g} "
        f"ring_full={dl.get('ring_full', 0):g}")
    adm = report.get("admission") or {}
    if adm:
        infl = adm.get("inflight") or {}
        sat = adm.get("saturated_ranks") or {}
        lines.append(
            f"admission: rejected={adm.get('rejected', 0):g} "
            f"shed={adm.get('shed', 0):g} inflight="
            + "/".join(f"{infl.get(c, 0):g}"
                       for c in ("high", "normal", "low"))
            + (" SATURATED=" + ",".join(
                f"rank{r}:{'+'.join(cls)}"
                for r, cls in sorted(sat.items(),
                                     key=lambda kv: int(kv[0])))
               if sat else ""))
    ranks = report.get("ranks") or {}
    if ranks:
        lines.append(
            "rank  state  beat_age   queue     step_s  health  numerics")
        for r, info in sorted(ranks.items(), key=lambda kv: int(kv[0])):
            verdicts = info.get("numerics")
            lines.append(
                f"{r:>4s}  {info.get('state', '?'):<5s} "
                f"{info.get('age_s', 0):>7.1f}s "
                f"{_fmt_us(info.get('queue_depth')):>7s} "
                f"{_fmt_us(info.get('step_s')):>10s}  "
                f"{str(info.get('health')):<6s}  "
                f"{','.join(verdicts) if verdicts else '-'}")
    return "\n".join(lines)


def _fmt_us(v) -> str:
    return "-" if v is None else f"{v:g}"


def _fleet_report_for(target: str) -> dict:
    """Resolve a ``--fleet`` target into a rollup dict: an ``http://``
    rank-0 endpoint (``/fleet`` is targeted automatically), a fleet KV
    directory (cold-scanned, no process needed), or a JSON report file
    (e.g. a saved ``curl .../fleet`` body)."""
    from urllib.parse import urlparse

    if _is_http(target):
        url = target
        if urlparse(target).path in ("", "/"):
            url = target.rstrip("/") + "/fleet"
        return json.loads(fetch_http(url))
    if os.path.isdir(target):
        from horovod_tpu.core import fleet

        return fleet.report_from_dir(target)
    with open(target) as fh:
        return json.loads(fh.read())


def _doctor_verdict_for(target: str) -> dict:
    """Resolve a ``--doctor`` target into a verdict dict: an ``http://``
    endpoint (``/doctor`` targeted automatically — triggers an on-demand
    diagnosis on that rank), a flight-dump directory (offline diagnosis
    over the embedded inspect tables), a saved verdict JSON, or a single
    flight-dump file."""
    from urllib.parse import urlparse

    if _is_http(target):
        url = target
        if urlparse(target).path in ("", "/"):
            url = target.rstrip("/") + "/doctor"
        return json.loads(fetch_http(url))
    from horovod_tpu.core import doctor

    if os.path.isdir(target):
        return doctor.diagnose_dumps(doctor.flight_dump_paths(target))
    with open(target) as fh:
        payload = json.loads(fh.read())
    if "findings" in payload:
        return payload  # a saved verdict (curl .../doctor body)
    if isinstance(payload.get("doctor"), dict):
        return payload["doctor"]  # a dump with an embedded verdict
    return doctor.diagnose_dumps([target])


def render_doctor(verdict: dict) -> str:
    """Human rendering of a doctor verdict: the attributed headline,
    then every finding grouped in ``_DOCTOR_KINDS`` priority order (a
    kind outside the vocabulary renders loudly as ``unknown-kind`` —
    the parity rule should have caught it first)."""
    lines: List[str] = []
    kind = verdict.get("kind")
    if kind is None:
        lines.append("doctor: no findings — nothing attributable "
                     f"(rank(s) reporting: "
                     f"{verdict.get('ranks_reporting', [])})")
        return "\n".join(lines)
    head = f"doctor: verdict={kind}"
    if verdict.get("tensor"):
        head += f" tensor='{verdict['tensor']}'"
    if verdict.get("ranks"):
        head += f" rank(s) {verdict['ranks']}"
    lines.append(head)
    lines.append(f"  reporting: rank(s) "
                 f"{verdict.get('ranks_reporting', [])} of "
                 f"{verdict.get('nproc', '?')}")
    order = {k: i for i, k in enumerate(_DOCTOR_KINDS)}
    findings = sorted(
        verdict.get("findings") or [],
        key=lambda f: order.get(f.get("kind"), len(order)))
    for f in findings:
        fk = f.get("kind")
        label = fk if fk in order else f"unknown-kind({fk})"
        lines.append(f"  - {label}: {f.get('detail', '')}")
    return "\n".join(lines)


def _is_xplane_dir(target: str) -> bool:
    if not os.path.isdir(target):
        return False
    from horovod_tpu.utils.profiler import trace_files

    try:
        return bool(trace_files(target))
    except Exception:
        return False


def _is_http(target: str) -> bool:
    return target.startswith(("http://", "https://"))


def fetch_http(target: str) -> str:
    """GET the exposition text from an ``HVD_TELEMETRY_PORT`` endpoint.
    A bare ``http://host:port`` targets ``/metrics``; a full path
    (``/metrics``, ``/healthz``) is used verbatim. Error statuses with a
    body are returned, not raised: ``/healthz`` deliberately answers 503
    while a warn-state verdict is live — exactly the moment the payload
    matters most."""
    import urllib.error
    import urllib.request
    from urllib.parse import urlparse

    url = target
    if urlparse(target).path in ("", "/"):
        url = target.rstrip("/") + "/metrics"
    try:
        with urllib.request.urlopen(url, timeout=5) as resp:
            return resp.read().decode("utf-8", "replace")
    except urllib.error.HTTPError as exc:
        body = exc.read().decode("utf-8", "replace")
        if body:
            return body
        raise


def xplane_samples(data: dict) -> List[Tuple[str, Dict[str, str], float]]:
    """Flatten an :func:`~horovod_tpu.utils.xplane.hbm_json` dict into
    exposition-shaped samples (``xplane_*`` names, the op class as a
    label) so ``--json`` is shape-identical with the other sources."""
    out: List[Tuple[str, Dict[str, str], float]] = []
    for key, val in data.items():
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            out.append((f"xplane_{key}", {}, float(val)))
    # Per-dtype byte split (HBM diet round 2): the bf16-vs-f32 audit
    # columns, dtype as a label like the op class.
    for dt, val in sorted((data.get("bytes_by_dtype_per_step")
                           or {}).items()):
        if isinstance(val, (int, float)):
            out.append(("xplane_bytes_per_step", {"dtype": dt},
                        float(val)))
    for cls, fields in sorted((data.get("classes") or {}).items()):
        for f in ("ms", "bytes"):
            if isinstance(fields.get(f), (int, float)):
                out.append((f"xplane_class_{f}", {"class": cls},
                            float(fields[f])))
        for dt, val in sorted((fields.get("by_dtype") or {}).items()):
            if isinstance(val, (int, float)):
                out.append(("xplane_class_dtype_bytes",
                            {"class": cls, "dtype": dt}, float(val)))
    return out


def _envelope(source: str, target: str,
              samples: List[Tuple[str, Dict[str, str], float]],
              doctor: dict = None) -> dict:
    env = {"source": source, "target": target,
           "samples": [{"name": n, "labels": l, "value": v}
                       for n, l, v in samples]}
    if doctor is not None:
        # The hang doctor's verdict rides INSIDE the one-envelope shape
        # (never replaces it): dashboards keyed on {source, target,
        # samples} keep parsing, doctor-aware ones read env["doctor"].
        env["doctor"] = doctor
    return env


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m horovod_tpu.utils.stats",
        description="Query horovod_tpu telemetry: an HVD_TELEMETRY_FILE "
                    "exposition file, an http://host:port endpoint "
                    "(HVD_TELEMETRY_PORT), an xplane capture dir, or "
                    "'live'.")
    ap.add_argument("target",
                    help="exposition file | http://host:port | xplane "
                         "capture dir | 'live'")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output (one envelope shape "
                         "for every source)")
    ap.add_argument("--fleet", action="store_true",
                    help="render the merged world rollup instead of "
                         "one rank's registry: target is the rank-0 "
                         "http endpoint (/fleet), a fleet KV directory "
                         "(HVD_FLEET_DIR — works with no live "
                         "process), or a saved report JSON file")
    ap.add_argument("--doctor", action="store_true",
                    help="render the hang doctor's attributed verdict: "
                         "target is a live rank's http endpoint "
                         "(/doctor — on-demand diagnosis), a "
                         "flight-dump directory (offline, works on a "
                         "dead world), or a saved verdict/dump JSON")
    ap.add_argument("--watch", type=float, metavar="SECONDS", default=None,
                    help="redraw the report every N seconds (exposition "
                         "file, http target or 'live'); Ctrl-C exits "
                         "cleanly")
    ap.add_argument("--steps", type=int, default=1,
                    help="steps in an xplane capture window (per-step "
                         "attribution)")
    args = ap.parse_args(argv)

    def render_once() -> int:
        if args.doctor:
            try:
                verdict = _doctor_verdict_for(args.target)
            except Exception as exc:
                print(f"cannot build doctor view from {args.target}: "
                      f"{exc}")
                return 1
            print(json.dumps(_envelope("doctor", args.target, [],
                                       doctor=verdict))
                  if args.json else render_doctor(verdict))
            return 0
        if args.fleet:
            try:
                report = _fleet_report_for(args.target)
            except Exception as exc:
                print(f"cannot build fleet view from {args.target}: {exc}")
                return 1
            print(json.dumps(report) if args.json
                  else render_fleet(report))
            return 0
        if args.target == "live":
            from horovod_tpu.core import telemetry

            if args.json:
                print(json.dumps(_envelope(
                    "live", "live",
                    parse_prometheus(telemetry.prometheus()))))
            else:
                print(telemetry.report())
            return 0
        if _is_http(args.target):
            try:
                text = fetch_http(args.target)
            except Exception as exc:
                print(f"cannot fetch {args.target}: {exc}")
                return 1
            samples = parse_prometheus(text)
            if args.json:
                if not samples and text.lstrip().startswith("{"):
                    # A /healthz target already answers machine-readable
                    # JSON (the sentinel health document, not metric
                    # samples) — pass it through instead of burying it
                    # in an empty-samples envelope.
                    print(text.strip())
                else:
                    print(json.dumps(_envelope("http", args.target,
                                               samples)))
            elif samples:
                print(render(samples))
            else:
                # A /healthz target returns JSON, not exposition text —
                # show it as-is rather than "no samples".
                print(text.rstrip("\n"))
            return 0
        if _is_xplane_dir(args.target):
            from horovod_tpu.utils import xplane

            if args.json:
                data = xplane.hbm_json(args.target, steps=args.steps)
                print(json.dumps(_envelope("xplane", args.target,
                                           xplane_samples(data))))
            else:
                print(xplane.hbm_report(args.target, steps=args.steps))
            return 0
        try:
            with open(args.target) as fh:
                text = fh.read()
        except OSError as exc:
            print(f"cannot read {args.target}: {exc}")
            return 1
        samples = parse_prometheus(text)
        if args.json:
            print(json.dumps(_envelope("file", args.target, samples)))
        else:
            print(render(samples))
        return 0

    # --watch: the poor-man's dashboard — file, http and 'live' targets
    # (stalls can be watched as they develop, from outside the process
    # via the HTTP endpoint). Ctrl-C is the documented way out — exit
    # cleanly, not with a KeyboardInterrupt stack trace.
    try:
        while True:
            rc = render_once()
            if args.watch is None or rc != 0:
                return rc
            time.sleep(args.watch)
            print()
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    raise SystemExit(main())
