"""Findings + run harness for the hvdcheck static-analysis suite.

Every checker returns a list of :class:`Finding`; the CLI and the tier-1
tests consume the same structures. Exit-code contract (pinned by
tests/test_analysis.py): 0 = clean tree, 2 = findings, 1 = the analysis
itself crashed (a parser stepped outside its subset — fix the parser or
the code that outgrew it; silence is never an option)."""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict, List


@dataclass
class Finding:
    rule: str      # rule id from RULE_CATALOG, e.g. "abi-struct"
    path: str      # repo-relative file the finding is anchored in
    line: int      # 1-based; 0 when the finding spans the whole file
    message: str

    def format(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        return f"{loc}: [{self.rule}] {self.message}"


# rule id -> one-line description (docs/static-analysis.md renders this
# catalog; tests pin that every emitted rule id is declared here).
RULE_CATALOG: Dict[str, str] = {
    "abi-struct": "C ABI struct fields must match the ctypes mirrors "
                  "field-for-field (name, order, width)",
    "abi-signature": "exported hvd_* C signatures must match the "
                     "argtypes/restype declarations in load_library()",
    "abi-callback": "C function-pointer typedefs must match the "
                    "CFUNCTYPE shapes (EXEC_FN/NEG_FN)",
    "parity-counters": "telemetry counter/gauge names must be fed by "
                       "both engines (python emit sites vs the native "
                       "stats sync)",
    "parity-stats-fields": "every native stats-sync field must exist in "
                           "the C hvd_engine_stats struct",
    "parity-spans": "timeline span names must match across the python "
                    "and C++ timeline writers",
    "parity-span-args": "timeline span-args keys must match across the "
                        "two engines' writers",
    "parity-grammar": "negotiation decision-grammar kinds emitted by "
                      "the python control plane must be handled by the "
                      "C++ parser",
    "parity-dtypes": "the C++ dtype-name table must match the python "
                     "wire-dtype table in order and spelling",
    "parity-wire-codes": "the C++ wire-policy code map must match "
                         "WIRE_CODES in core/engine.py",
    "parity-ops": "the C++ HvdOp enum must match the python op codes",
    "parity-latency": "latency histogram bucket edges (kLatencyBucketsS "
                      "vs telemetry.LATENCY_BUCKETS_S) and the native "
                      "_LATENCY_HISTS field targets must match — world "
                      "rollups merge per-rank histograms exactly",
    "parity-doctor": "the inspect record shape (ENGINE_INSPECT_KEYS vs "
                     "Engine.inspect vs the C++ Inspect writer) and the "
                     "hang-doctor verdict vocabulary (doctor."
                     "VERDICT_KINDS vs stats._DOCTOR_KINDS) must match, "
                     "names and order — the doctor diffs records across "
                     "engines and ranks",
    "tf-bridge-group": "no per-tensor blocking engine bridge inside a "
                       "TF py_function loop (use _bridge_group: "
                       "submit-all-then-wait)",
    "engine-lifecycle": "never destroy the C++ engine; abandon paths "
                        "must not join a wedged engine",
    "phase-names": "a literal handed to phase(...) must be a name of "
                   "the vocabulary in common/phases.py",
    "donate-mutate": "a buffer handed over with donate=True must not "
                     "be mutated before synchronize in the same scope",
    "eager-drain": "trainer broadcast_state methods must pull state to "
                   "host first and drain before returning",
    "lock-order": "lock acquisitions must follow the documented "
                  "hierarchy: engine lock > pool lock > telemetry locks",
    "entrypoint-imports": "run.py must stay import-free at module "
                          "level (stdlib only)",
    "fault-site-registry": "every faultline site referenced in "
                           "tests/docs/specs must resolve to a declared "
                           "site+mode, and every declared site must be "
                           "threaded (its guard called from source)",
}


def repo_root(start: str = None) -> str:
    """The repository root: the directory holding ``horovod_tpu/``.
    Resolved from this file so the CLI works from any cwd."""
    if start is not None:
        return start
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(here))


def run_all(root: str = None) -> List[Finding]:
    """Every checker against the live tree rooted at ``root``."""
    from horovod_tpu.analysis import abi, invariants, parity

    root = repo_root(root)
    findings: List[Finding] = []
    findings.extend(abi.check(root))
    findings.extend(parity.check(root))
    findings.extend(invariants.check(root))
    for f in findings:
        if f.rule not in RULE_CATALOG:
            raise AssertionError(
                f"checker emitted undeclared rule id {f.rule!r} — add it "
                "to RULE_CATALOG (and docs/static-analysis.md)")
    return findings


def render(findings: List[Finding], as_json: bool) -> str:
    if as_json:
        return json.dumps({
            "findings": [vars(f) for f in findings],
            "count": len(findings),
            "rules": sorted({f.rule for f in findings}),
        })
    if not findings:
        return "hvdcheck: clean (0 findings)"
    lines = [f.format() for f in findings]
    lines.append(f"hvdcheck: {len(findings)} finding"
                 f"{'' if len(findings) == 1 else 's'}")
    return "\n".join(lines)
