"""hvdcheck (horovod_tpu/analysis): the static-analysis suite itself.

Tiers in this file:

- live-tree: every checker runs against the REAL repository and must
  come back clean — this is what wires the analyzer into tier-1 CI, so
  an ABI/parity/invariant drift fails the commit it lands in;
- mutation corpus: copies of the real hvdcore.cc / ctypes binding with
  one seeded skew each (swapped C fields, widened ctypes field, skewed
  argtypes, renamed C++ counter field, renamed span) — the ABI/parity
  checkers must catch every one, proving they diff the real files and
  not a cached model of them;
- rule fixtures: hand-written violation snippets for each invariant
  rule (per-tensor TF bridge, engine destroy/abandon-join, donate-then-
  mutate, missing eager drain, lock inversion, non-stdlib entrypoint
  import);
- CLI: the exit-code contract (0 clean / 2 findings) on a mini tree;
- slow (HVD_SLOW_TESTS=1): the native-engine TSan smoke —
  HVD_SANITIZE=thread build + a multi-threaded engine workout under
  LD_PRELOAD'd libtsan with the shipped suppression file.
"""

import ast
import json
import os
import shutil
import subprocess
import sys

import pytest

from horovod_tpu import analysis
from horovod_tpu.analysis import abi, invariants, parity, report

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_slow_on = os.environ.get("HVD_SLOW_TESTS", "").lower() in (
    "1", "true", "yes", "on")


# ---------------------------------------------------------------------------
# live tree: the analyzer IS tier-1 CI
# ---------------------------------------------------------------------------


def test_live_tree_is_clean():
    findings = analysis.run_all(REPO)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_every_rule_is_cataloged_and_documented():
    doc = open(os.path.join(REPO, "docs", "static-analysis.md")).read()
    for rule in report.RULE_CATALOG:
        assert rule in doc, f"rule {rule!r} missing from the catalog doc"


# ---------------------------------------------------------------------------
# mutation corpus: the ABI/parity checkers diff the REAL files
# ---------------------------------------------------------------------------

_CORE_FILES = ("engine.py", "native_engine.py", "bufferpool.py",
               "timeline.py", "telemetry.py", "doctor.py")


def _mini_root(tmp_path):
    """A copy of exactly the files the checkers read, so mutations can
    be seeded without touching the live tree."""
    core = tmp_path / "horovod_tpu" / "core"
    native = core / "native"
    utils = tmp_path / "horovod_tpu" / "utils"
    native.mkdir(parents=True)
    utils.mkdir()
    for f in _CORE_FILES:
        shutil.copy(os.path.join(REPO, "horovod_tpu", "core", f), core)
    for f in ("hvdcore.cc", "__init__.py"):
        shutil.copy(os.path.join(REPO, "horovod_tpu", "core", "native", f),
                    native)
    shutil.copy(os.path.join(REPO, "horovod_tpu", "utils", "stats.py"),
                utils)
    shutil.copy(os.path.join(REPO, "horovod_tpu", "run.py"),
                tmp_path / "horovod_tpu")
    return str(tmp_path)


def _edit(root, rel, old, new):
    path = os.path.join(root, rel)
    src = open(path).read()
    assert old in src, f"mutation anchor not found in {rel}: {old!r}"
    open(path, "w").write(src.replace(old, new))


_CC = os.path.join("horovod_tpu", "core", "native", "hvdcore.cc")
_BINDING = os.path.join("horovod_tpu", "core", "native", "__init__.py")
_NATIVE_PY = os.path.join("horovod_tpu", "core", "native_engine.py")


def test_mini_root_baseline_is_clean(tmp_path):
    root = _mini_root(tmp_path)
    findings = analysis.run_all(root)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_abi_catches_swapped_c_struct_fields(tmp_path):
    root = _mini_root(tmp_path)
    _edit(root, _CC, "int itemsize;\n  int average;",
          "int average;\n  int itemsize;")
    rules = {f.rule for f in abi.check(root)}
    assert rules == {"abi-struct"}


def test_abi_catches_skewed_ctypes_field(tmp_path):
    """The issue's canonical seed: a ctypes mirror field narrowed behind
    the C struct's back."""
    root = _mini_root(tmp_path)
    _edit(root, _BINDING, '("wire_bytes", ctypes.c_longlong),',
          '("wire_bytes", ctypes.c_int),')
    findings = abi.check(root)
    assert any(f.rule == "abi-struct" and "wire_bytes" in f.message
               for f in findings), findings


def test_abi_catches_new_c_field_missing_from_mirror(tmp_path):
    root = _mini_root(tmp_path)
    _edit(root, _CC, "long long admission_bytes_low;\n};",
          "long long admission_bytes_low;\n  long long new_counter;\n};")
    findings = abi.check(root)
    assert any(f.rule == "abi-struct" and "new_counter" in f.message
               for f in findings), findings


def test_abi_catches_enqueue_n_argtype_skew(tmp_path):
    """The batched-submit entry point is machine-diffed like every other
    hvd_* symbol: narrowing the request-array pointer in the ctypes
    mirror must be named."""
    root = _mini_root(tmp_path)
    _edit(root, _BINDING,
          "lib.hvd_engine_enqueue_n.argtypes = [\n"
          "        ctypes.c_void_p, ctypes.POINTER(HvdRequest), "
          "ctypes.c_int,\n"
          "        ctypes.POINTER(ctypes.c_longlong), ctypes.c_char_p]",
          "lib.hvd_engine_enqueue_n.argtypes = [\n"
          "        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,\n"
          "        ctypes.POINTER(ctypes.c_longlong), ctypes.c_char_p]")
    findings = abi.check(root)
    assert any(f.rule == "abi-signature"
               and "hvd_engine_enqueue_n" in f.message
               for f in findings), findings


def test_abi_catches_argtype_skew(tmp_path):
    root = _mini_root(tmp_path)
    _edit(root, _BINDING,
          "lib.hvd_engine_poll.argtypes = [ctypes.c_void_p, "
          "ctypes.c_longlong]",
          "lib.hvd_engine_poll.argtypes = [ctypes.c_void_p, ctypes.c_int]")
    findings = abi.check(root)
    assert any(f.rule == "abi-signature" and "hvd_engine_poll" in f.message
               for f in findings), findings


def test_abi_catches_callback_typedef_skew(tmp_path):
    root = _mini_root(tmp_path)
    _edit(root, _CC,
          "typedef int (*hvd_negotiate_fn)(void* ctx, const char* "
          "table_json,\n                                char** "
          "decision_out);",
          "typedef int (*hvd_negotiate_fn)(void* ctx, const char* "
          "table_json,\n                                long long epoch,"
          "\n                                char** decision_out);")
    findings = abi.check(root)
    assert any(f.rule == "abi-callback" for f in findings), findings


def test_parity_catches_renamed_cxx_counter_field(tmp_path):
    """The issue's canonical seed: a C++ stats counter renamed without
    the stats sync following."""
    root = _mini_root(tmp_path)
    _edit(root, _CC, "long long fused_batches;", "long long fused_groups;")
    rules = {f.rule for f in parity.check(root)}
    assert "parity-stats-fields" in rules
    # ...and the ABI checker flags the layout skew independently.
    assert any(f.rule == "abi-struct" for f in abi.check(root))


def test_parity_catches_renamed_ring_counter_field(tmp_path):
    """The batched-submit stats tail (ring_full/ring_spins/
    pool_bound_hits) is covered by the same stats-field diff as the
    legacy counters."""
    root = _mini_root(tmp_path)
    _edit(root, _CC, "long long ring_full;", "long long ring_stalls;")
    rules = {f.rule for f in parity.check(root)}
    assert "parity-stats-fields" in rules
    assert any(f.rule == "abi-struct" for f in abi.check(root))


def test_parity_catches_renamed_cxx_span(tmp_path):
    root = _mini_root(tmp_path)
    _edit(root, _CC, '"MEMCPY_IN_FUSION_BUFFER"', '"MEMCPY_INTO_FUSION"')
    findings = parity.check(root)
    assert any(f.rule == "parity-spans" and "MEMCPY_INTO_FUSION"
               in f.message for f in findings), findings


def test_parity_catches_python_only_counter(tmp_path):
    root = _mini_root(tmp_path)
    _edit(root, os.path.join("horovod_tpu", "core", "engine.py"),
          'tele.REGISTRY.counter("engine.cycles").inc()',
          'tele.REGISTRY.counter("engine.cycles_total").inc()')
    findings = parity.check(root)
    assert any(f.rule == "parity-counters" for f in findings), findings


def test_parity_catches_dtype_table_skew(tmp_path):
    root = _mini_root(tmp_path)
    _edit(root, _CC, '"float32",  "float64", "float16"',
          '"float32",  "float16", "float64"')
    findings = parity.check(root)
    assert any(f.rule == "parity-dtypes" for f in findings), findings


def test_parity_catches_unhandled_decision_kind(tmp_path):
    root = _mini_root(tmp_path)
    _edit(root, _NATIVE_PY, 'lines.append(f"w {decision.idle_backoff_s}")',
          'lines.append(f"z {decision.idle_backoff_s}")')
    findings = parity.check(root)
    assert any(f.rule == "parity-grammar" and "'z'" in f.message
               for f in findings), findings


def test_parity_catches_wire_code_skew(tmp_path):
    root = _mini_root(tmp_path)
    _edit(root, _CC, 'case 2: return "fp8";', 'case 3: return "fp8";')
    findings = parity.check(root)
    assert any(f.rule == "parity-wire-codes" for f in findings), findings


def test_abi_catches_skewed_wire_dcn_field(tmp_path):
    """The per-tier DCN wire policy rides the C ABI (hvd_request.wire_dcn);
    widening the ctypes mirror behind the C struct's back must be named."""
    root = _mini_root(tmp_path)
    _edit(root, _BINDING, '("wire_dcn", ctypes.c_int),',
          '("wire_dcn", ctypes.c_longlong),')
    findings = abi.check(root)
    assert any(f.rule == "abi-struct" and "wire_dcn" in f.message
               for f in findings), findings


def test_parity_catches_renamed_tier_counter_field(tmp_path):
    """The per-tier wire byte counters (wire_bytes_dcn/_ici) join the
    machine-diffed stats vocabulary: renaming the C++ side without the
    stats sync following is named by both checkers."""
    root = _mini_root(tmp_path)
    _edit(root, _CC, "long long wire_bytes_dcn;",
          "long long wire_bytes_slow;")
    rules = {f.rule for f in parity.check(root)}
    assert "parity-stats-fields" in rules
    assert any(f.rule == "abi-struct" for f in abi.check(root))


def test_parity_catches_renamed_tier_span_arg(tmp_path):
    """Timeline span args carry the per-tier policy ("wire_dcn"); the
    C++ emitter drifting from the python vocabulary is a span-args skew."""
    root = _mini_root(tmp_path)
    _edit(root, _CC, 'out += ", \\"wire_dcn\\": \\"";',
          'out += ", \\"dcn_wire\\": \\"";')
    findings = parity.check(root)
    assert any(f.rule == "parity-span-args" for f in findings), findings


def test_parity_catches_skewed_latency_bucket_edge(tmp_path):
    """The issue's canonical seed: one C++ bucket edge nudged — merged
    world histograms would silently corrupt every fleet quantile."""
    root = _mini_root(tmp_path)
    _edit(root, _CC, "1e-4, 3e-4, 1e-3", "2e-4, 3e-4, 1e-3")
    findings = parity.check(root)
    assert any(f.rule == "parity-latency" and "kLatencyBucketsS"
               in f.message for f in findings), findings


def test_parity_catches_renamed_latency_struct_field(tmp_path):
    """A renamed hvd_engine_latency field skews both the _LATENCY_HISTS
    fold target (parity) and the ctypes mirror layout (abi)."""
    root = _mini_root(tmp_path)
    _edit(root, _CC, "long long phase_exec[13];",
          "long long phase_execute[13];")
    findings = parity.check(root)
    assert any(f.rule == "parity-latency" and "phase_exec" in f.message
               for f in findings), findings
    assert any(f.rule == "abi-struct" for f in abi.check(root))


def test_parity_doctor_catches_skewed_cxx_inspect_key(tmp_path):
    """The issue's canonical seed: one C++ inspect-record JSON key
    renamed — the doctor's cross-rank/cross-engine record diff would
    silently lose that field's attribution."""
    root = _mini_root(tmp_path)
    _edit(root, _CC, '\\"phase_age_us\\":', '\\"phaseage_us\\":')
    findings = parity.check(root)
    assert any(f.rule == "parity-doctor" and "phaseage_us" in f.message
               for f in findings), findings


def test_parity_doctor_catches_renamed_verdict_kind(tmp_path):
    """A verdict kind renamed in the classifier without the stats-CLI
    consumer table following — every console would render it as
    unknown-kind."""
    root = _mini_root(tmp_path)
    _edit(root, os.path.join("horovod_tpu", "core", "doctor.py"),
          '"missing_submitter"', '"missing_sub"')
    findings = parity.check(root)
    assert any(f.rule == "parity-doctor" and "missing_sub" in f.message
               for f in findings), findings


def test_parity_doctor_catches_python_record_skew(tmp_path):
    """The python twin's record builder drifting from the declared
    contract is caught from the engine.py side alone."""
    root = _mini_root(tmp_path)
    _edit(root, os.path.join("horovod_tpu", "core", "engine.py"),
          "phase_age_us=int((now - e.phase_since) * 1e6),",
          "phase_age=int((now - e.phase_since) * 1e6),")
    findings = parity.check(root)
    assert any(f.rule == "parity-doctor" and "ENGINE_INSPECT_KEYS"
               in f.message for f in findings), findings


def test_abi_catches_skewed_priority_field(tmp_path):
    """The serving-plane priority class rides the C ABI
    (hvd_request.priority); widening the ctypes mirror behind the C
    struct's back must be named."""
    root = _mini_root(tmp_path)
    _edit(root, _BINDING, '("priority", ctypes.c_int),',
          '("priority", ctypes.c_longlong),')
    findings = abi.check(root)
    assert any(f.rule == "abi-struct" and "priority" in f.message
               for f in findings), findings


def test_parity_catches_renamed_admission_counter_field(tmp_path):
    """The admission counters (engine.admission.rejected/shed) join the
    machine-diffed stats vocabulary: renaming the C++ field without the
    stats sync following is named by both checkers."""
    root = _mini_root(tmp_path)
    _edit(root, _CC, "long long admission_rejected;",
          "long long admission_refused;")
    rules = {f.rule for f in parity.check(root)}
    assert "parity-stats-fields" in rules
    assert any(f.rule == "abi-struct" for f in abi.check(root))


def test_parity_catches_renamed_admission_span_arg(tmp_path):
    """Timeline span args carry the serving-plane class ("priority");
    the C++ emitter drifting from the python vocabulary is a span-args
    skew."""
    root = _mini_root(tmp_path)
    _edit(root, _CC, 'out += ", \\"priority\\": \\"";',
          'out += ", \\"prio_class\\": \\"";')
    findings = parity.check(root)
    assert any(f.rule == "parity-span-args" for f in findings), findings


def test_parity_doctor_catches_renamed_overload_verdict(tmp_path):
    """The serving-plane 'overload' verdict renamed in the classifier
    without the stats-CLI consumer table following — same contract as
    the other doctor kinds."""
    root = _mini_root(tmp_path)
    _edit(root, os.path.join("horovod_tpu", "core", "doctor.py"),
          '"overload"', '"overloaded"')
    findings = parity.check(root)
    assert any(f.rule == "parity-doctor" and "overloaded" in f.message
               for f in findings), findings


def test_parity_catches_renamed_latency_instrument(tmp_path):
    """A latency instrument renamed on the native fold side only — the
    vocabularies the two engines feed must stay identical."""
    root = _mini_root(tmp_path)
    _edit(root, _NATIVE_PY, '("engine.latency.allreduce", "allreduce"),',
          '("engine.latency.allreduce_s", "allreduce"),')
    findings = parity.check(root)
    assert any(f.rule == "parity-counters"
               and "engine.latency.allreduce" in f.message
               for f in findings), findings


# ---------------------------------------------------------------------------
# invariant rule fixtures: each rule catches its seeded violation
# ---------------------------------------------------------------------------


def _findings_for(snippet: str, rule_fn, rel="fixture.py"):
    tree = ast.parse(snippet)
    return rule_fn(tree, rel)


def test_rule_tf_bridge_catches_per_tensor_blocking_loop():
    bad = '''
import tensorflow as tf

def broken_group(tensors, names):
    def fn(*ts):
        e = get_engine()
        outs = []
        for name, t in zip(names, ts):
            h = e.allreduce_async(name, t.numpy(), True)
            outs.append(e.synchronize(h))  # blocking per tensor: wedges
        return outs
    return tf.py_function(fn, tensors, Tout=[t.dtype for t in tensors])
'''
    findings = _findings_for(bad, invariants.check_tf_bridge)
    assert len(findings) == 1 and findings[0].rule == "tf-bridge-group"


def test_rule_tf_bridge_allows_submit_all_then_wait():
    good = '''
import tensorflow as tf

def grouped(tensors, names):
    def fn(*ts):
        e = get_engine()
        handles = [e.allreduce_async(n, t.numpy(), True)
                   for n, t in zip(names, ts)]
        outs = []
        for h in handles:
            outs.append(e.synchronize(h))
        return outs
    return tf.py_function(fn, tensors, Tout=[t.dtype for t in tensors])
'''
    assert _findings_for(good, invariants.check_tf_bridge) == []


def test_rule_engine_lifecycle_catches_destroy_and_abandon_join():
    bad = '''
def shutdown(self):
    self._lib.hvd_engine_join(self._ptr)
    self._lib.hvd_engine_destroy(self._ptr)  # UB: waiters in WaitMeta

def abandon(self):
    self._lib.hvd_engine_join(self._ptr)  # never returns: loop is wedged
    self._stall_thread.join()
'''
    findings = _findings_for(bad, invariants.check_engine_lifecycle)
    assert {f.rule for f in findings} == {"engine-lifecycle"}
    msgs = " ".join(f.message for f in findings)
    assert "hvd_engine_destroy" in msgs
    assert "hvd_engine_join" in msgs
    assert "_stall_thread" in msgs


def test_rule_donate_mutate_catches_write_after_handoff():
    bad = '''
def step(e, grad):
    h = e.allreduce_async("grad", grad, True, donate=True)
    grad[0] = 0.0  # mutates the engine's in-place reference
    return e.synchronize(h)
'''
    findings = _findings_for(bad, invariants.check_donate_mutate)
    assert len(findings) == 1 and findings[0].rule == "donate-mutate"


def test_rule_donate_mutate_allows_mutation_after_synchronize():
    good = '''
def step(e, grad):
    h = e.allreduce_async("grad", grad, True, donate=True)
    out = e.synchronize(h)
    grad[0] = 0.0  # handle retired: ownership is back
    return out
'''
    assert _findings_for(good, invariants.check_donate_mutate) == []


def test_rule_eager_drain_catches_device_first_broadcast():
    bad = '''
class Trainer:
    def broadcast_state(self, root_rank=0):
        # sharded device arrays handed straight to the eager broadcast
        self.params = broadcast_pytree(self.params, root_rank)
        self.opt_state = broadcast_pytree(self.opt_state, root_rank)
'''
    findings = _findings_for(bad, invariants.check_eager_drain)
    assert {f.rule for f in findings} == {"eager-drain"}
    assert len(findings) == 2  # no host pull AND no drain


def test_rule_eager_drain_allows_host_first_pattern():
    good = '''
class Trainer:
    def broadcast_state(self, root_rank=0):
        host = jax.device_get((self.params, self.opt_state))
        params, opt_state = host
        self.params = broadcast_pytree(params, root_rank)
        self.opt_state = broadcast_pytree(opt_state, root_rank)
        jax.block_until_ready((self.params, self.opt_state))
'''
    assert _findings_for(good, invariants.check_eager_drain) == []


def test_rule_phase_names_catches_a_name_outside_the_vocabulary():
    bad = '''
def exchange(x):
    with _phases.phase("hvd_exchange"):
        return psum(x)
'''
    findings = _findings_for(bad, invariants.check_phase_names)
    assert len(findings) == 1 and findings[0].rule == "phase-names"
    assert "'hvd_exchange'" in findings[0].message


def test_rule_phase_names_allows_the_vocabulary_and_other_calls():
    good = '''
def exchange(x, name):
    with _phases.phase("hvd_allreduce"), _phase("hvd_pack"):
        log(phase="start")          # a keyword, not the scope
        return phase(name)          # not a literal: refused at trace time
'''
    assert _findings_for(good, invariants.check_phase_names) == []


def test_rule_lock_order_catches_inversion():
    bad = '''
class BufferPool:
    def checkout(self, count):
        with self._lock:
            self.engine._complete(None, None, None)  # pool -> engine
            return None

class Engine:
    def _complete(self, e, result, err):
        with self._lock:
            self._handles.pop(0, None)

    def _enqueue(self, entry):
        with self.pool._lock:       # nested inversion: pool held...
            with self._lock:        # ...while taking the engine lock
                pass
'''
    findings = invariants.check_lock_order({"engine.py": ast.parse(bad)})
    assert findings, "lock inversion not caught"
    assert all(f.rule == "lock-order" for f in findings)
    msgs = " ".join(f.message for f in findings)
    assert "checkout" in msgs and "_enqueue" in msgs


def test_rule_lock_order_allows_documented_hierarchy():
    good = '''
class Engine:
    def _enqueue(self, entry):
        with self._lock:
            self._pending[entry.name] = entry

class BufferPool:
    def checkout_tracked(self, count):
        with self._lock:
            self._c_hits.inc()  # telemetry leaf under pool lock: rank 2>3
            return None
'''
    assert invariants.check_lock_order({"engine.py": ast.parse(good)}) == []


def test_rule_entrypoint_imports_catches_framework_import(tmp_path):
    root = _mini_root(tmp_path)
    _edit(root, os.path.join("horovod_tpu", "run.py"), "import argparse",
          "import argparse\nimport jax")
    findings = invariants.check_entrypoint_imports(root)
    assert any(f.rule == "entrypoint-imports" and "'jax'" in f.message
               for f in findings), findings


def test_rule_entrypoint_imports_clean_on_live_entrypoints():
    assert invariants.check_entrypoint_imports(REPO) == []


def test_rule_fault_site_registry_clean_on_live_tree():
    assert invariants.check_fault_sites(REPO) == []


def _fault_root(tmp_path):
    """A mini root with the real faultline.py + one consumer + one
    chaos-spec reference, for seeding registry skews."""
    core = tmp_path / "horovod_tpu" / "core"
    core.mkdir(parents=True)
    shutil.copy(os.path.join(REPO, "horovod_tpu", "core", "faultline.py"),
                core)
    (core / "consumer.py").write_text(
        "from horovod_tpu.core import faultline as flt\n\n\n"
        "def submit(name):\n"
        "    injected = flt.engine_submit(name)\n"
        "    flt.engine_admit_burst()\n"
        "    flt.kv_get(name)\n"
        "    flt.kv_set(name, 'v')\n"
        "    flt.kv_try_get(name)\n"
        "    flt.heartbeat()\n"
        "    flt.engine_exec('allreduce')\n"
        "    flt.pool_exhausted()\n"
        "    flt.ckpt_write()\n"
        "    flt.preempt_signal()\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_chaos.py").write_text(
        "SPEC = 'hb.beat:skip:*@8'\n")
    return str(tmp_path)


def test_rule_fault_site_registry_catches_renamed_site(tmp_path):
    """The satellite's canonical seed: a site renamed in the registry
    while a chaos spec still references the old name — the spec would
    silently inject nothing."""
    root = _fault_root(tmp_path)
    assert invariants.check_fault_sites(root) == []
    _edit(root, os.path.join("horovod_tpu", "core", "faultline.py"),
          '"hb.beat"', '"hb.pulse"')
    findings = invariants.check_fault_sites(root)
    assert any("hb.beat" in f.message and f.rule == "fault-site-registry"
               for f in findings), findings


def test_rule_fault_site_registry_catches_unknown_mode(tmp_path):
    root = _fault_root(tmp_path)
    with open(os.path.join(root, "tests", "test_chaos.py"), "a") as fh:
        # (Assembled so the LIVE tree's scan of this very test file
        # does not see a bad spec literal.)
        fh.write("BAD = '" + "engine.exec" + ":explode:1'\n")
    findings = invariants.check_fault_sites(root)
    assert any("'explode'" in f.message for f in findings), findings


def test_rule_fault_site_registry_catches_unthreaded_site(tmp_path):
    """A site whose guard helper is never called from source is declared
    but inert — chaos specs naming it test nothing."""
    root = _fault_root(tmp_path)
    _edit(root, os.path.join("horovod_tpu", "core", "consumer.py"),
          "    flt.ckpt_write()\n", "")
    findings = invariants.check_fault_sites(root)
    assert any("ckpt.write" in f.message and "not threaded" in f.message
               for f in findings), findings


def test_rule_fault_site_registry_exempts_negative_fixtures(tmp_path):
    """Deliberately-invalid specs inside FaultSpecError rejection tests
    are negative fixtures, not site references."""
    root = _fault_root(tmp_path)
    with open(os.path.join(root, "tests", "test_chaos.py"), "a") as fh:
        fh.write(
            "import pytest\n"
            "from horovod_tpu.core import faultline as flt\n\n\n"
            "def test_bad_spec_rejected():\n"
            "    with pytest.raises(flt.FaultSpecError):\n"
            "        flt.configure('no.such" + ":delay:1')\n")
    assert invariants.check_fault_sites(root) == []


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------


def test_cli_exit_codes_and_json(tmp_path):
    from horovod_tpu.analysis.__main__ import main

    # Clean mini tree -> 0.
    root = _mini_root(tmp_path)
    assert main(["--root", root, "--json"]) == 0
    # Seed one violation -> 2.
    _edit(root, _CC, "long long fused_batches;", "long long fused_groups;")
    assert main(["--root", root]) == 2
    assert main(["--list-rules"]) == 0


def test_cli_subprocess_on_live_tree():
    """The `python -m horovod_tpu.analysis` spelling of the tier-1 run."""
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.analysis", "--json"],
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-2000:])
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["count"] == 0


# ---------------------------------------------------------------------------
# sanitizer wiring
# ---------------------------------------------------------------------------


def test_sanitize_mode_validation(monkeypatch):
    from horovod_tpu.core import native

    monkeypatch.delenv("HVD_SANITIZE", raising=False)
    assert native.sanitize_mode() == ""
    monkeypatch.setenv("HVD_SANITIZE", "off")
    assert native.sanitize_mode() == ""
    monkeypatch.setenv("HVD_SANITIZE", "thread")
    assert native.sanitize_mode() == "thread"
    monkeypatch.setenv("HVD_SANITIZE", "memory")
    with pytest.raises(native.NativeBuildError):
        native.sanitize_mode()


def test_tsan_suppression_file_ships():
    from horovod_tpu.core import native

    assert os.path.exists(native.TSAN_SUPPRESSIONS)
    active = [ln.strip() for ln in open(native.TSAN_SUPPRESSIONS)
              if ln.strip() and not ln.strip().startswith("#")]
    assert active, "suppression file has no active entries"
    # Host-noise suppressions only: nothing may match engine frames.
    assert all("hvdcore" not in ln for ln in active), active


@pytest.mark.slow
@pytest.mark.skipif(not _slow_on,
                    reason="TSan smoke is the opt-in tier: "
                           "HVD_SLOW_TESTS=1 to run")
def test_tsan_native_engine_smoke():
    """HVD_SANITIZE=thread produces a working instrumented build, and a
    multi-threaded native-engine workout under it reports ZERO races
    (with the shipped suppression file quieting uninstrumented-host
    noise only)."""
    from horovod_tpu.core import native

    lib = native.build_library(mode="thread")
    runtime = native.sanitizer_runtime("thread")
    env = dict(os.environ)
    env["LD_PRELOAD"] = runtime
    env["HVD_SANITIZE"] = "thread"
    env["TSAN_OPTIONS"] = (f"suppressions={native.TSAN_SUPPRESSIONS} "
                           "exitcode=66 halt_on_error=0")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests",
                                      "tsan_smoke_worker.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert os.path.exists(lib)
    assert proc.returncode == 0, (proc.returncode, proc.stdout[-2000:],
                                  proc.stderr[-4000:])
    assert "TSAN_SMOKE_OK" in proc.stdout
    assert "WARNING: ThreadSanitizer" not in proc.stderr, \
        proc.stderr[-4000:]


@pytest.mark.slow
@pytest.mark.skipif(not _slow_on,
                    reason="ASan smoke is the opt-in tier: "
                           "HVD_SLOW_TESTS=1 to run")
def test_asan_native_engine_smoke():
    """HVD_SANITIZE=address produces a working instrumented build, and
    the same multi-threaded native-engine workout as the TSan smoke
    reports ZERO AddressSanitizer errors (PR 14 follow-up — the
    ASan-tier mirror). Leak detection stays OFF: the engine leaks
    by DOCTRINE (quiesce-then-leak, parked donations), and the
    uninstrumented CPython host would drown the report regardless —
    this smoke is about overflows/use-after-free in the C++ core."""
    from horovod_tpu.core import native

    lib = native.build_library(mode="address")
    runtime = native.sanitizer_runtime("address")
    env = dict(os.environ)
    env["LD_PRELOAD"] = runtime
    env["HVD_SANITIZE"] = "address"
    env["ASAN_OPTIONS"] = ("detect_leaks=0 abort_on_error=0 "
                           "exitcode=66 allocator_may_return_null=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests",
                                      "tsan_smoke_worker.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert os.path.exists(lib)
    assert proc.returncode == 0, (proc.returncode, proc.stdout[-2000:],
                                  proc.stderr[-4000:])
    assert "TSAN_SMOKE_OK" in proc.stdout  # same worker, same marker
    assert "ERROR: AddressSanitizer" not in proc.stderr, \
        proc.stderr[-4000:]
