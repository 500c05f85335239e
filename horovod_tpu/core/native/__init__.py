"""ctypes binding + build for libhvdcore (the native engine).

Mirrors the reference's loader role (reference: horovod/common/__init__.py:
51-56 loads the C library RTLD_GLOBAL; setup.py builds it). Here the
library is a single translation unit built on demand with g++ — no MPI, no
framework headers — so it compiles anywhere in seconds and is cached next
to the source.

A built library is reused only when it was built from the source and the
flags at hand: the hash of both is part of its file name
(``libhvdcore.<key>.so``), and a file is published under that name only
by an atomic rename after a successful build. File times play no part — a
tree copied to another machine (ignored files included, mtimes not
necessarily kept) can never load a library built from an older
``hvdcore.cc`` against today's ctypes mirrors.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import re
import subprocess
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "hvdcore.cc")

_lock = threading.Lock()
_lib = None

# Default build promoted to -Wall -Wextra -Werror (hvdcheck satellite):
# the engine core compiles warning-clean, and a new warning is a build
# failure the commit it lands in, not reviewer homework.
_BASE_FLAGS = ["-std=c++17", "-fPIC", "-shared", "-pthread",
               "-Wall", "-Wextra", "-Werror"]

# HVD_SANITIZE={thread,address} rebuild modes: (sanitizer flags, artifact
# stem, optimization flags). Each mode publishes its own artifact next to
# the source (the default lib is never clobbered by a sanitized build, so
# flipping the env var back costs nothing). -O1 -fno-omit-frame-pointer
# is the sanitizer-recommended pairing: usable stacks, tolerable slowdown.
_SANITIZE_MODES = {
    "": ([], "libhvdcore", ["-O2", "-g"]),
    "thread": (["-fsanitize=thread", "-fno-omit-frame-pointer"],
               "libhvdcore.tsan", ["-O1", "-g"]),
    "address": (["-fsanitize=address", "-fno-omit-frame-pointer"],
                "libhvdcore.asan", ["-O1", "-g"]),
}

# TSan suppressions for the Python-hosted run (tests + LD_PRELOAD
# recipe in docs/static-analysis.md). The engine code itself must stay
# report-clean — these only quiet runtime noise from non-instrumented
# host code.
TSAN_SUPPRESSIONS = os.path.join(_DIR, "tsan.supp")


class NativeBuildError(RuntimeError):
    pass


def sanitize_mode() -> str:
    """The HVD_SANITIZE build mode ('', 'thread' or 'address'); unknown
    spellings fail fast rather than silently building unsanitized."""
    mode = os.environ.get("HVD_SANITIZE", "").strip().lower()
    if mode in ("0", "off", "none", "false"):
        mode = ""
    if mode not in _SANITIZE_MODES:
        raise NativeBuildError(
            f"unknown HVD_SANITIZE mode {mode!r}: expected 'thread' or "
            "'address'")
    return mode


def sanitizer_runtime(mode: str = "thread") -> str:
    """Path to the sanitizer runtime to LD_PRELOAD when loading a
    sanitized libhvdcore into an UNinstrumented interpreter (loading it
    bare fails with a static-TLS error). Resolved through the same
    compiler that builds the library."""
    name = {"thread": "libtsan.so", "address": "libasan.so"}[mode]
    proc = subprocess.run(["g++", f"-print-file-name={name}"],
                          capture_output=True, text=True)
    path = proc.stdout.strip()
    if proc.returncode != 0 or not os.path.exists(path):
        raise NativeBuildError(f"cannot locate {name} via g++")
    return os.path.realpath(path)


def _keyed_path(src: str, stem: str, flags) -> str:
    """``<dir of src>/<stem>.<key>.so`` where key hashes the source bytes
    and the compiler flags."""
    h = hashlib.sha256()
    with open(src, "rb") as fh:
        h.update(fh.read())
    h.update("\0".join(flags).encode())
    return os.path.join(os.path.dirname(src),
                        f"{stem}.{h.hexdigest()[:16]}.so")


def _build_keyed(src: str, stem: str, flags, force: bool = False,
                 libs=()) -> str:
    """Return the library for (src, flags, libs), compiling it unless the
    keyed file is already there. ``libs`` follow the source on the
    command line (link order). Caller holds ``_lock``."""
    out = _keyed_path(src, stem, list(flags) + list(libs))
    if not force and os.path.exists(out):
        return out
    # pid-suffixed temp: concurrent processes (multi-controller first
    # run on a shared filesystem) must not compile into the same file;
    # os.replace makes the final publish atomic whoever wins.
    tmp = f"{out}.tmp.{os.getpid()}"
    proc = subprocess.run(
        ["g++"] + list(flags) + [src, "-o", tmp] + list(libs),
        capture_output=True, text=True)
    if proc.returncode != 0:
        raise NativeBuildError(
            f"failed to build {stem}: {proc.stderr[-2000:]}")
    os.replace(tmp, out)
    # Libraries of other sources/flags under this stem (and the unkeyed
    # name older trees used) can never be loaded again: drop them so a
    # long-lived checkout does not grow one .so per edit.
    stale = re.compile(re.escape(stem) + r"(\.[0-9a-f]{16})?\.so$")
    for name in os.listdir(os.path.dirname(out)):
        if stale.match(name) and name != os.path.basename(out):
            try:
                os.unlink(os.path.join(os.path.dirname(out), name))
            except OSError:
                pass
    return out


def _engine_build(mode: str):
    san_flags, stem, opt_flags = _SANITIZE_MODES[mode]
    return stem, opt_flags + _BASE_FLAGS + san_flags


def library_path(mode: Optional[str] = None) -> str:
    """Where the engine library for the source on disk lives (whether or
    not it has been built yet)."""
    stem, flags = _engine_build(sanitize_mode() if mode is None else mode)
    return _keyed_path(_SRC, stem, flags)


def build_library(force: bool = False, mode: Optional[str] = None) -> str:
    """Compile the engine library unless one built from this source and
    these flags exists; returns the path. ``mode`` overrides HVD_SANITIZE
    ('' = the plain production build)."""
    stem, flags = _engine_build(sanitize_mode() if mode is None else mode)
    with _lock:
        return _build_keyed(_SRC, stem, flags, force)


_SHIELD_SRC = os.path.join(_DIR, "termshield.cc")
_shield_lib = None


def load_termshield():
    """Build + load the std::terminate parking shim (see termshield.cc)
    and install it. Elastic-only callers; raises NativeBuildError when
    the toolchain is unavailable. Cached + idempotent."""
    global _shield_lib
    with _lock:
        if _shield_lib is not None:
            return _shield_lib
        path = _build_keyed(_SHIELD_SRC, "libtermshield",
                            ["-O2"] + _BASE_FLAGS, libs=["-ldl"])
        lib = ctypes.CDLL(path)
        lib.hvd_termshield_install.argtypes = []
        lib.hvd_termshield_install()
        _shield_lib = lib
        return lib


class HvdRequest(ctypes.Structure):
    _fields_ = [
        ("op", ctypes.c_int),
        ("dtype_num", ctypes.c_int),
        ("itemsize", ctypes.c_int),
        ("average", ctypes.c_int),
        ("root_rank", ctypes.c_int),
        # Engine wire policy code (core/engine.py WIRE_CODES).
        ("wire", ctypes.c_int),
        # Per-tier DCN policy code (hierarchical two-phase route) —
        # mutually exclusive with a nonzero `wire`.
        ("wire_dcn", ctypes.c_int),
        ("prescale", ctypes.c_double),
        # Seconds to the request's deadline at executor-call time (0 =
        # none; negative = already overdue — enforcement is the engine
        # loop/watchdog's, this is data-plane advice only).
        ("deadline_s", ctypes.c_double),
        ("names", ctypes.c_char_p),
        ("data", ctypes.c_void_p),
        # Where same-size results must be written: == data unless the
        # input was DONATED (caller-owned, read-only to the engine), in
        # which case the engine supplies a pooled bounce buffer.
        ("out", ctypes.c_void_p),
        ("count", ctypes.c_longlong),
        ("ndim", ctypes.c_int),
        ("shape", ctypes.c_longlong * 8),
        # Batched-submit plane (hvd_engine_enqueue_n): per-request
        # ownership-handoff flag, honored element-by-element like the
        # single-enqueue `donate` argument. Engine->executor requests
        # always carry 0 here.
        ("donate", ctypes.c_int),
        # Priority class code (core/engine.py PRIORITY_CODES; lower
        # drains first) — the serving-plane scheduling key.
        ("priority", ctypes.c_int),
    ]


class HvdResult(ctypes.Structure):
    _fields_ = [
        ("data", ctypes.c_void_p),
        ("nbytes", ctypes.c_longlong),
        ("ndim", ctypes.c_int),
        ("shape", ctypes.c_longlong * 8),
        # Executor-measured host->device staging seconds; the engine turns
        # it into the WAIT_FOR_DATA timeline span.
        ("stage_s", ctypes.c_double),
        # Bytes the mesh collective shipped (payload+scales under a
        # quantized wire policy) and the compressed-policy subset.
        ("wire_bytes", ctypes.c_longlong),
        ("wire_compressed", ctypes.c_longlong),
        # Per-tier byte split of the hierarchical two-phase route (zero
        # on flat routes): DCN = quantized 1/L cross-tier payload, ICI =
        # full-width intra-tier share.
        ("wire_dcn", ctypes.c_longlong),
        ("wire_ici", ctypes.c_longlong),
        ("error", ctypes.c_char * 256),
    ]


class HvdStats(ctypes.Structure):
    """Execution-side telemetry snapshot — field layout MUST stay in sync
    with hvd_engine_stats in hvdcore.cc."""

    _fields_ = [
        ("submitted", ctypes.c_longlong * 3),
        ("submitted_bytes", ctypes.c_longlong),
        ("completed", ctypes.c_longlong),
        ("errors", ctypes.c_longlong),
        ("fused_batches", ctypes.c_longlong),
        ("fused_tensors", ctypes.c_longlong),
        ("fused_bytes", ctypes.c_longlong),
        ("cycles", ctypes.c_longlong),
        ("cycle_seconds", ctypes.c_double),
        ("queue_depth", ctypes.c_longlong),
        ("wire_bytes", ctypes.c_longlong),
        ("wire_bytes_compressed", ctypes.c_longlong),
        # Per-tier split of the hierarchical route (engine.wire_bytes
        # .dcn/.ici counter parity with the python engine).
        ("wire_bytes_dcn", ctypes.c_longlong),
        ("wire_bytes_ici", ctypes.c_longlong),
        # Buffer-pool accounting (hvdcore BufferPool — fed into the same
        # engine.pool.* telemetry the python pool feeds).
        ("pool_hits", ctypes.c_longlong),
        ("pool_misses", ctypes.c_longlong),
        ("pool_checkouts", ctypes.c_longlong),
        ("pool_bytes_resident", ctypes.c_longlong),
        # Deadline/cancel plane (engine.deadline_exceeded /
        # engine.cancelled counter parity with the python engine).
        ("deadline_exceeded", ctypes.c_longlong),
        ("cancelled", ctypes.c_longlong),
        # Batched-submit plane: submit-ring pressure and name-bound pool
        # reuse (engine.ring.{full,spins} / engine.pool.bound_hits).
        ("ring_full", ctypes.c_longlong),
        ("ring_spins", ctypes.c_longlong),
        ("pool_bound_hits", ctypes.c_longlong),
        # Serving-plane admission control (engine.admission.* counter/
        # gauge parity with the python engine): boundary rejections,
        # deadline-aware sheds, and per-class in-flight counts.
        ("admission_rejected", ctypes.c_longlong),
        ("admission_shed", ctypes.c_longlong),
        ("admission_inflight_high", ctypes.c_longlong),
        ("admission_inflight_normal", ctypes.c_longlong),
        ("admission_inflight_low", ctypes.c_longlong),
        ("admission_bytes_high", ctypes.c_longlong),
        ("admission_bytes_normal", ctypes.c_longlong),
        ("admission_bytes_low", ctypes.c_longlong),
    ]


class HvdLatency(ctypes.Structure):
    """Latency/phase-residency histogram snapshot — field layout MUST
    stay in sync with hvd_engine_latency in hvdcore.cc. Each instrument
    is 13 raw bucket counts over the shared LATENCY_BUCKETS_S edges
    (last = +Inf overflow) plus an exact value sum; native_engine.py
    folds count deltas into the registry via Histogram.add_counts."""

    _fields_ = [
        ("allreduce", ctypes.c_longlong * 13),
        ("allgather", ctypes.c_longlong * 13),
        ("broadcast", ctypes.c_longlong * 13),
        ("phase_queue", ctypes.c_longlong * 13),
        ("phase_negotiate", ctypes.c_longlong * 13),
        ("phase_memcpy", ctypes.c_longlong * 13),
        ("phase_exec", ctypes.c_longlong * 13),
        ("deadline_margin", ctypes.c_longlong * 13),
        # Per-priority-class serving-plane latency split
        # (engine.latency.class.* histogram parity).
        ("class_high", ctypes.c_longlong * 13),
        ("class_normal", ctypes.c_longlong * 13),
        ("class_low", ctypes.c_longlong * 13),
        ("allreduce_sum", ctypes.c_double),
        ("allgather_sum", ctypes.c_double),
        ("broadcast_sum", ctypes.c_double),
        ("phase_queue_sum", ctypes.c_double),
        ("phase_negotiate_sum", ctypes.c_double),
        ("phase_memcpy_sum", ctypes.c_double),
        ("phase_exec_sum", ctypes.c_double),
        ("deadline_margin_sum", ctypes.c_double),
        ("class_high_sum", ctypes.c_double),
        ("class_normal_sum", ctypes.c_double),
        ("class_low_sum", ctypes.c_double),
    ]


EXEC_FN = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p,
                           ctypes.POINTER(HvdRequest),
                           ctypes.POINTER(HvdResult))

# Negotiation control-plane hook: (ctx, table_json, decision_out) -> rc.
# The callback must write an hvd_alloc()'d C string into *decision_out.
NEG_FN = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p, ctypes.c_char_p,
                          ctypes.POINTER(ctypes.c_void_p))


def load_library():
    """Build if needed, load, and declare signatures. Cached."""
    global _lib
    if _lib is not None:
        return _lib
    path = build_library()
    lib = ctypes.CDLL(path)
    lib.hvd_engine_create.restype = ctypes.c_void_p
    lib.hvd_engine_create.argtypes = [ctypes.c_double, ctypes.c_longlong,
                                      ctypes.c_double, ctypes.c_char_p]
    lib.hvd_engine_set_executor.argtypes = [ctypes.c_void_p, EXEC_FN,
                                            ctypes.c_void_p]
    lib.hvd_engine_set_params.argtypes = [ctypes.c_void_p, ctypes.c_double,
                                          ctypes.c_longlong]
    lib.hvd_engine_get_params.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_longlong)]
    lib.hvd_engine_set_sort_by_name.argtypes = [ctypes.c_void_p,
                                                ctypes.c_int]
    lib.hvd_engine_set_negotiator.argtypes = [ctypes.c_void_p, NEG_FN,
                                              ctypes.c_void_p]
    lib.hvd_engine_set_negotiation_active.argtypes = [ctypes.c_void_p,
                                                      ctypes.c_int]
    lib.hvd_alloc.restype = ctypes.c_void_p
    lib.hvd_alloc.argtypes = [ctypes.c_longlong]
    lib.hvd_engine_enqueue.restype = ctypes.c_longlong
    lib.hvd_engine_enqueue.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
        ctypes.c_int, ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong),
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_double,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_double, ctypes.c_char_p]
    lib.hvd_engine_set_admission.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong),
        ctypes.POINTER(ctypes.c_longlong)]
    lib.hvd_engine_enqueue_n.restype = ctypes.c_int
    lib.hvd_engine_enqueue_n.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(HvdRequest), ctypes.c_int,
        ctypes.POINTER(ctypes.c_longlong), ctypes.c_char_p]
    lib.hvd_engine_poll.restype = ctypes.c_int
    lib.hvd_engine_poll.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
    lib.hvd_engine_cancel.restype = ctypes.c_int
    lib.hvd_engine_cancel.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
    lib.hvd_engine_wait_meta.restype = ctypes.c_int
    lib.hvd_engine_wait_meta.argtypes = [
        ctypes.c_void_p, ctypes.c_longlong,
        ctypes.POINTER(ctypes.c_longlong), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_longlong), ctypes.c_char_p]
    lib.hvd_engine_copy_result.restype = ctypes.c_int
    lib.hvd_engine_copy_result.argtypes = [
        ctypes.c_void_p, ctypes.c_longlong, ctypes.c_void_p,
        ctypes.c_longlong]
    lib.hvd_engine_drop.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
    lib.hvd_engine_pending.restype = ctypes.c_longlong
    lib.hvd_engine_pending.argtypes = [ctypes.c_void_p]
    lib.hvd_engine_pending_names.restype = ctypes.c_longlong
    lib.hvd_engine_pending_names.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong]
    lib.hvd_engine_inspect.restype = ctypes.c_longlong
    lib.hvd_engine_inspect.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong]
    lib.hvd_engine_get_stats.argtypes = [ctypes.c_void_p,
                                         ctypes.POINTER(HvdStats)]
    lib.hvd_engine_get_latency.argtypes = [ctypes.c_void_p,
                                           ctypes.POINTER(HvdLatency)]
    lib.hvd_engine_timeline_instant.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p]
    lib.hvd_engine_timeline_meta.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p]
    lib.hvd_engine_timeline_now.restype = ctypes.c_longlong
    lib.hvd_engine_timeline_now.argtypes = [ctypes.c_void_p]
    lib.hvd_engine_recent_events.restype = ctypes.c_longlong
    lib.hvd_engine_recent_events.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong]
    lib.hvd_engine_shutdown.argtypes = [ctypes.c_void_p]
    lib.hvd_engine_join.argtypes = [ctypes.c_void_p]
    lib.hvd_engine_destroy.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib
