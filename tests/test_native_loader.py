"""The native loader reuses a library only when it was built from the
source and flags at hand (core/native/__init__.py): the key is a hash in
the file name, and file times play no part."""

import os
import shutil
import time

import pytest

from horovod_tpu.core import native

pytestmark = pytest.mark.skipif(shutil.which("g++") is None,
                                reason="no C++ toolchain")

_FLAGS = ["-O0", "-fPIC", "-shared"]


def _write(path, body):
    path.write_text(f'extern "C" int answer() {{ return {body}; }}\n')


def test_rebuilds_on_source_change_and_ignores_newer_stale_library(tmp_path):
    import ctypes

    src = tmp_path / "thing.cc"
    _write(src, 1)
    with native._lock:
        first = native._build_keyed(str(src), "libthing", _FLAGS)
    assert ctypes.CDLL(first).answer() == 1
    with native._lock:  # same source, same flags: reused, not rebuilt
        stamp = os.path.getmtime(first)
        assert native._build_keyed(str(src), "libthing", _FLAGS) == first
    assert os.path.getmtime(first) == stamp

    # A copy of the tree need not keep mtimes: make the OLD library (and
    # a library under the unkeyed name older trees used) look NEWER than
    # the source that is about to change.
    legacy = tmp_path / "libthing.so"
    shutil.copy(first, legacy)
    _write(src, 2)
    future = time.time() + 3600
    os.utime(first, (future, future))
    os.utime(legacy, (future, future))

    with native._lock:
        second = native._build_keyed(str(src), "libthing", _FLAGS)
    assert second != first
    assert ctypes.CDLL(second).answer() == 2
    # What can never be loaded again is gone.
    assert not os.path.exists(first) and not legacy.exists()

    # Other flags are another library, too.
    with native._lock:
        third = native._build_keyed(str(src), "libthing",
                                    _FLAGS + ["-DX=1"])
    assert third != second


def test_engine_library_name_carries_the_key():
    path = native.library_path(mode="")
    assert os.path.basename(path).startswith("libhvdcore.")
    assert path == native.build_library(mode="")
    assert native.library_path(mode="thread") != path
